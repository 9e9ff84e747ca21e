"""hbm_peak_frac (layer: device): ``peak_bytes_in_use / bytes_limit`` of
the fullest chip, read after the window."""


def read(ctx):
    shares = [m["peak_bytes_in_use"] / m["bytes_limit"]
              for m in ctx["run"]["memory"] if m["bytes_limit"]]
    return max(shares) if shares else None
