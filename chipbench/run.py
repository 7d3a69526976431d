#!/usr/bin/env python3
"""Chip benchmark of the stencil engine: one cell, one run.

    python chipbench/run.py --workload star2d_r2.sweep --seed 7 \
        --seconds 10 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; everything else is found by the names it gives:

  configs/<config>.json     the deployment: stencil, dtype, grid, mesh
  traffic/<traffic>.json    the mix; its ``path`` names the path module
  paths/<path>.py           set-up, measured window and check of a path
  limits/<workload>.json    the limit of each number the check compares
  metrics/<metric>.py       one reader per per-layer metric

A run sets up (state made on the device from ``--seed``, plan, compile
from the checkout's compile cache, warm-up), measures for ``--seconds``,
then checks what the window produced against the plain reference
(``reference.py``).  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are read from its trace; with
``--trace 0`` the end-to-end metrics are reported.  The last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``; ``checks`` comes last.
A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
PLATFORM = "tpu"


class BenchError(RuntimeError):
    """The run cannot be made as asked (unknown name, missing chip)."""


# -- lookups by name --------------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)} for {name!r}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def applies(m, kind):
        if "workloads" in m:
            return workload in m["workloads"]
        if kind == "per_layer":
            return applies(e2e[m["moves"]], "end_to_end")
        return True
    return [m for m in bench[kind] if applies(m, kind)]


class Cell:
    """Everything one run needs, found by the cell's name.

    ``limits`` is None until ``limits/<workload>.json`` exists:
    ``control.py`` measures what it is set from, and a run refuses the
    cell until then.
    """

    def __init__(self, workload: str, bench: dict | None = None):
        self.bench = bench if bench is not None else load_benchmark()
        self.entry = find(self.bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = load_json("configs", self.entry["config"])
        self.traffic = load_json("traffic", self.entry["traffic"])
        limits = HERE / "limits" / f"{workload}.json"
        self.limits = (json.loads(limits.read_text()) if limits.is_file()
                       else None)
        self.path = load_module("paths", self.traffic["path"])


# -- the chip ---------------------------------------------------------------

def chips(n: int):
    """The first ``n`` TPU devices; a run never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise BenchError(f"no TPU: JAX found platform "
                         f"{devices[0].platform!r}; this benchmark measures "
                         f"the chip only")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices[:n]


class CompileCounter:
    """Counts traces, lowerings and compiles (or cache loads) from JAX's
    own monitoring events, and sums their host-clock seconds."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.count += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds


class Readings:
    """What a per-layer metric reader gets: the cell, the window's facts,
    the parsed trace, the set-up spans and the chip's peaks."""

    def __init__(self, cell: Cell, facts: dict, trace, spans: dict,
                 peaks: dict):
        self.cell = cell
        self.facts = facts
        self.trace = trace
        self.spans = spans
        self.peaks = peaks


def read_per_layer(cell: Cell, readings: Readings) -> dict:
    out = {}
    for m in cell_metrics(cell.bench, cell.name, "per_layer"):
        value = load_module("metrics", m["name"]).read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(cell: Cell, measured: dict) -> dict:
    out = {}
    for m in cell_metrics(cell.bench, cell.name, "end_to_end"):
        if m["name"] not in measured:
            raise BenchError(f"the {cell.traffic['path']!r} path measured "
                             f"no {m['name']!r}")
        out[m["name"]] = {"value": float(measured[m["name"]]),
                          "unit": m["unit"]}
    return out


def judge(limits: dict, compared: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every one is
    at or under it (a missing number fails)."""
    checks, ok = {}, True
    for name, lim in limits["compared"].items():
        value = compared.get(name)
        checks[name] = {"value": value, "limit": lim["limit"]}
        ok = ok and value is not None and value <= lim["limit"]
    return ok, checks


def profile_options():
    """Host events from the runtime and from annotations; no Python
    function tracing, which would slow the host path being measured."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no count, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import jax
    from chipbench import trace as tr
    from chipbench import work

    t_chip = time.perf_counter()
    kind = devices[0].device_kind
    peaks = work.peaks_for(kind) if devices[0].platform == PLATFORM else {}
    counter = CompileCounter()
    path = cell.path.Path(cell.config, cell.traffic, seed, devices)
    path.setup()
    # set-up leaves much garbage (traced and compiled programs); collect it
    # now and keep the survivors out of later collections, so that no
    # collection of it pauses the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    n0, s0 = counter.mark()
    spans = {"compile_s": s0, "compiles": n0}
    print(f"setup: {t_chip - t_start:.4f} s from the process's start to the "
          f"chip, {setup_s - (t_chip - t_start):.4f} s in the path's set-up, "
          f"{s0:.4f} s of it tracing, lowering and compiling or loading",
          flush=True)

    parsed = None
    if trace:
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d, profiler_options=profile_options())
            try:
                win = path.window(seconds)
            finally:
                jax.profiler.stop_trace()
            parsed = tr.load(tr.find_xplane(d), len(devices))
    else:
        win = path.window(seconds)
    n1, _ = counter.mark()
    cache_misses = win["facts"].get("plan_cache_misses", 0)
    print(f"window: {n1 - n0} compiles or traces, {cache_misses} plan-cache "
          f"misses (both must be 0)", flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes(devices)}
    path.release()
    compared = path.check()
    correct, checks = judge(cell.limits, compared)
    correct = correct and win["failed"] == 0

    if trace:
        readings = Readings(cell, win["facts"], parsed, spans, peaks)
        metrics = read_per_layer(cell, readings)
        busy, window_s = parsed.busy_s()
        device.update(busy_s=busy, window_s=window_s)
        breakdown = parsed.breakdown()
    else:
        metrics = end_to_end(cell, dict(win["metrics"], setup_s=setup_s))
        breakdown = None
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives at a fixed path inside the checkout; JAX
    # reads the variable when it is imported, and the program's own helper
    # turns the cache on from it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        cell = Cell(args.workload)
        if cell.limits is None:
            raise BenchError(f"no file chipbench/limits/{cell.name}.json: "
                             f"chipbench/control.py measures the readings "
                             f"its limits are set from")
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        devices = chips(cell.chips)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
