"""pallas_busy_frac (layer: kernels): device time in Pallas kernels over
device busy time, summed over chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    pallas = sum(d["pallas_s"] for d in tr["devices"])
    busy = sum(d["busy_s"] for d in tr["devices"])
    return pallas / busy if pallas > 0 and busy > 0 else None
