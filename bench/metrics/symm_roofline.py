"""symm_roofline (layer: kernels), in %: the roofline time of the step's
``symm`` calls routed to Pallas (``bench/work.py``), over the device
time of the ``tpu_custom_call`` ops under ``blas.symm.pallas``."""
from bench import scopes


def read(ctx):
    return scopes.pallas_roofline(ctx, "symm")
