"""wire_device_s (layer: mesh wires), in s: device self time per traced
step under the mesh wires, mean over chips: the ``repro.blas`` mesh
routes (``blas.<op>.{1d,2d,3d,ring}``) and Muon's own 1d wire
(``optim.muon_1d``), their collectives and their local products alike;
``bench/scopes.py`` joins the trace to the HLO."""
from bench import scopes

WIRES = r"(^|/)(blas\.\w+\.(1d|2d|3d|ring)|optim\.muon_1d)(/|$)"


def read(ctx):
    return scopes.scope_s(ctx, WIRES)
