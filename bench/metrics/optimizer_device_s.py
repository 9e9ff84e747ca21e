"""optimizer_device_s (layer: optimizer), in s: device self time per
traced step under the ``optim.*`` scopes (Muon's momentum, NS chains
and update), mean over chips; ``bench/scopes.py`` joins the trace to
the HLO."""
from bench import scopes


def read(ctx):
    got = scopes.read(ctx)
    if not got:
        return None
    return got["join"].buckets["optimizer"] / got["steps"]
