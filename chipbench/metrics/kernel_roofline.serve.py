"""Mosaic kernels' share of their roofline in the serve window."""
from chipbench.reduce import kernel_roofline as read  # noqa: F401
