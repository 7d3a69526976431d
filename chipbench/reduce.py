"""Per-layer readings from a window's trace and facts.

On a mesh each reading is taken per device and the worst device is
reported: the lowest roofline share, the highest idle share and halo
time.  A reading with nothing to read (no trace, no kernel) is ``None``,
and the metric is left out.
"""
from __future__ import annotations

from chipbench import work


def kernel_roofline(r) -> float | None:
    """Least time the window's useful work could take at the published
    peaks, over the time the device spent in Mosaic kernels, in %."""
    if r.trace is None:
        return None
    bound, binds = work.bound_s(r.facts["flops_per_device"],
                                r.facts["bytes_per_device"], r.peaks)
    shares = [bound / (d.time("kernel") * 1e-9) * 100
              for d in r.trace.devices if d.time("kernel") > 0]
    if not shares:
        return None
    print(f"kernel roofline: bound {bound:.6f} s per device, {binds} binds",
          flush=True)
    return min(shares)


def per_call_ms(r, kind: str) -> float | None:
    """Device time per call in ops of ``kind`` (worst device), in ms."""
    if r.trace is None or not r.facts.get("calls"):
        return None
    return max(d.time(kind) for d in r.trace.devices) * 1e-6 \
        / r.facts["calls"]


def idle_share(r) -> float | None:
    """Share of the window with no op on the device (worst device), in %."""
    if r.trace is None:
        return None
    from chipbench.trace import length
    w = r.trace.window[1] - r.trace.window[0]
    return max(100.0 * (1.0 - length(d.busy()) / w)
               for d in r.trace.devices)


def batch_fill(r) -> float | None:
    """Requests over the states the buckets carried, in %."""
    req, pad = r.facts.get("requests"), r.facts.get("padded_states")
    if not req:
        return None
    return 100.0 * req / (req + pad)
