"""exposed_collective_frac (layer: mesh wires): the part of the
collective time in which no other operation ran on the same chip, over
the traced window, mean over chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    if not any(d["collective_s"] for d in tr["devices"]):
        return None
    exp = [d["exposed_collective_s"] for d in tr["devices"]]
    return sum(exp) / len(exp) / tr["window_s"]
