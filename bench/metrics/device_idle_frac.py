"""device_idle_frac (layer: device): the share of the traced window in
which no operation ran on a chip, 1 - busy / window, mean over chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    busy = sum(d["busy_s"] for d in tr["devices"]) / len(tr["devices"])
    return 1.0 - busy / tr["window_s"]
