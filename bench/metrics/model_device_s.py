"""model_device_s (layer: model), in s: device self time per traced step
under the ``train.loss`` scope (forward, backward and remat recompute),
mean over chips; ``bench/scopes.py`` joins the trace to the HLO."""
from bench import scopes


def read(ctx):
    got = scopes.read(ctx)
    if not got:
        return None
    b = got["join"].buckets
    return sum(b[k] for k in scopes.MODEL) / got["steps"]
