"""Production mesh construction and the per-chip roofline table.

FUNCTIONS, not module constants — importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any JAX initialization).
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["make_production_mesh", "make_mesh", "HardwareSpec", "TPU_V5E",
           "TPU_V5P", "HARDWARE", "DEVICE_KINDS", "get_hardware",
           "hardware_for_device"]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline constants for the target chip."""
    name: str
    peak_flops_bf16: float      # per chip, FLOP/s
    hbm_bw: float               # bytes/s
    ici_bw: float               # bytes/s per link
    hbm_bytes: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four links.
TPU_V5E = HardwareSpec(name="tpu_v5e", peak_flops_bf16=197e12,
                       hbm_bw=819e9, ici_bw=50e9, hbm_bytes=16e9)
# Google Cloud documentation, "TPU v5p": 459 TFLOP/s bf16, 95 GB of HBM at
# 2,765 GB/s, 4,800 Gbit/s of interconnect over six links.
TPU_V5P = HardwareSpec(name="tpu_v5p", peak_flops_bf16=459e12,
                       hbm_bw=2765e9, ici_bw=100e9, hbm_bytes=95e9)

HARDWARE = {hw.name: hw for hw in (TPU_V5E, TPU_V5P)}

#: ``jax.Device.device_kind`` -> roofline constants.
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E, "TPU v5": TPU_V5P}


def get_hardware(name: str) -> HardwareSpec:
    """Look up roofline constants by chip name (planner CLI / plan JSON)."""
    if name not in HARDWARE:
        raise KeyError(f"unknown hardware {name!r}; known: {sorted(HARDWARE)}")
    return HARDWARE[name]


def hardware_for_device(device=None) -> HardwareSpec:
    """Roofline constants of ``device`` (default: the first JAX device).

    A chip missing from :data:`DEVICE_KINDS` is an error, never a default:
    planning against another chip's peaks would rank plans wrongly.
    """
    device = device if device is not None else jax.devices()[0]
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no roofline constants for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests, elastic re-mesh) over ``devices`` (default:
    the first ``prod(shape)`` of ``jax.devices()``)."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types,
                         devices=devices)
