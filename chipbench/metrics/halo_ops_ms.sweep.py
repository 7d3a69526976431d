"""Device time per call in pads, slices and copies around the kernels."""
from chipbench.reduce import per_call_ms


def read(r):
    return per_call_ms(r, "other")
