"""syrk_roofline (layer: kernels), in %: the roofline time of the step's
``syrk`` calls routed to Pallas (``bench/work.py``), over the device
time of the ``tpu_custom_call`` ops under ``blas.syrk.pallas``."""
from bench import scopes


def read(ctx):
    return scopes.pallas_roofline(ctx, "syrk")
