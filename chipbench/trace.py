"""The device trace of a window, reduced to intervals.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` and keeps, for each TPU device plane, the ops
of its ``XLA Ops`` line (one op at a time on the core), and, for the
host, the events of every ``/host:CPU`` line.  Ops are classified by kind, never by name, so that a
renamed kernel is still found:

  kernel      a Mosaic kernel: ``custom_call_target="tpu_custom_call"``
  collective  a collective: ``collective-permute``, ``all-reduce``, ...
  other       everything else: pads, slices, copies, fusions

The window is the host span ``chipbench.window`` that the path module
puts around its measured window; every interval is clipped to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "chipbench.window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "collective-broadcast")
_OP = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def classify(text: str) -> str:
    if KERNEL_MARK in text:
        return "kernel"
    m = _OP.match(text)
    opcode = m.group(2) if m else text
    if any(opcode.startswith(c) for c in COLLECTIVES):
        return "collective"
    return "other"


def op_label(text: str) -> str:
    """A short stable label of an op for the breakdown: its HLO name with
    the numeric suffix dropped, and the kind for kernels."""
    m = _OP.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0].lstrip("%")
    name = re.sub(r"(\.\d+)+$", "", name)
    return f"{name} [kernel]" if KERNEL_MARK in text else name


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Op:
    start: float        # ns, on the trace's clock
    end: float
    kind: str
    label: str


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Op]                   # the XLA Ops line, one op at a time

    def busy(self) -> list[tuple[float, float]]:
        return merge((o.start, o.end) for o in self.ops)

    def time(self, kind: str) -> float:
        return length(merge((o.start, o.end) for o in self.ops
                            if o.kind == kind))


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]          # ns
    devices: list[Device]
    host: list[tuple[str, str, float, float]]   # (line, name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> tuple[float, float]:
        """Seconds in which an op ran, averaged over the devices, and the
        window's length."""
        per = [length(d.busy()) * 1e-9 for d in self.devices]
        return sum(per) / len(per), self.window_s

    def idle_gaps(self, device: Device) -> list[tuple[float, float]]:
        return subtract([self.window], device.busy())

    def host_doing(self, times) -> list[str]:
        """What the host was doing at each of ``times`` (ascending): the
        innermost event open on the Python thread's line, else on any
        other host line."""
        lines: dict[str, list] = {}
        for line, name, s, e in self.host:
            if name != WINDOW_SPAN:
                lines.setdefault(line, []).append((s, e, name))
        found = {}
        for line, evs in lines.items():
            evs.sort()
            stack, i = [], 0
            for t in times:
                while i < len(evs) and evs[i][0] <= t:
                    stack.append(evs[i])
                    i += 1
                while stack and stack[-1][1] <= t:
                    stack.pop()
                if stack:
                    found.setdefault(t, {})[line] = stack[-1][2]
        out = []
        for t in times:
            at = found.get(t, {})
            out.append(at.get("python") or next(iter(at.values()),
                                                "no host event"))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the idle time by what
        the host was doing, summed over devices, in seconds."""
        ops: dict[str, float] = {}
        gaps: dict[str, float] = {}
        idle = []
        for d in self.devices:
            for o in d.ops:
                ops[o.label] = ops.get(o.label, 0.0) + (o.end - o.start) * 1e-9
            idle += self.idle_gaps(d)
        idle.sort(key=lambda g: g[0] + g[1])
        for (s, e), what in zip(idle, self.host_doing(
                [(s + e) / 2 for s, e in idle])):
            gaps[what] = gaps.get(what, 0.0) + (e - s) * 1e-9
        order = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        gorder = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in order],
                "idle_gaps": [[k, v] for k, v in gorder]}


def _clip(s: float, e: float, w) -> tuple[float, float] | None:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def load(path: str, n_devices: int) -> Trace:
    """Parse an ``.xplane.pb``: the first ``n_devices`` TPU planes and the
    host lines, clipped to the ``chipbench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, dev_planes = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((line.name, ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev_planes.append(plane)
    spans = [(s, e) for _, n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    dev_planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = []
    for plane in dev_planes[:n_devices]:
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window)
                if iv is not None:
                    ops.append(Op(iv[0], iv[1], classify(ev.name),
                                  op_label(ev.name)))
        devices.append(Device(plane.name, ops))
    if len(devices) < n_devices:
        raise ValueError(f"{path} holds {len(devices)} TPU planes, the run "
                         f"used {n_devices}")
    host = [(ln, n, s, e) for ln, n, s, e in host
            if e > window[0] and s < window[1]]
    return Trace(window, devices, host)
