"""pallas_roofline (layer: kernels), in %: the roofline time of the
calls the step's routes sent to Pallas, over the Pallas device time.

Roofline time of a call = max(flop / peak, bytes / HBM bandwidth), both
from the call's shapes, batch included (``bench/work.py``: the paper's
flop counts, operands read once, output written once at its fill)."""
from bench import work


def read(ctx):
    tr = ctx.get("trace")
    calls = ctx["run"]["work"]["pallas_calls"]
    if not tr or not calls:
        return None
    pallas = sum(d["pallas_s"] for d in tr["devices"]) / len(tr["devices"])
    if pallas <= 0:
        return None
    need = work.calls_roofline_s(calls, ctx["peak"]) * ctx["run"]["trace"]["steps"]
    return 100.0 * need / pallas
