"""step_mfu (layer: whole train step), in %: the flop one step requires
(model forward and backward plus the Newton-Schulz chains, counted from
shapes by ``bench/work.py``) times the traced steps, over the traced
window's host-clock length times chips times the chip's peak."""


def read(ctx):
    tr = ctx["run"].get("trace")
    if not tr or not tr["window_s"]:
        return None
    flop = ctx["run"]["work"]["step_flop"] * tr["steps"]
    return 100.0 * flop / (tr["window_s"] * ctx["chips"]
                           * ctx["peak"]["flop_per_s"])
