"""collective_frac (layer: mesh wires): device time in collective
operations over the traced window, mean over chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    coll = [d["collective_s"] for d in tr["devices"]]
    if not any(coll):
        return None
    return sum(coll) / len(coll) / tr["window_s"]
