"""Chaos serving benchmark: recovered throughput and tail latency under
seeded fault injection, sync-loop vs background-stepper mode.

For each fault rate in RATES a seeded :class:`repro.api.FaultPlan`
injects ``serve.settle`` faults (the deferred-device-error shape under
JAX async dispatch) into a warm serving pass, and the benchmark records
what the retry ladder COSTS: warm whole-stream wall clock, recovered
throughput (states/s — every request still completes, within
``REORDER_TOL`` of the fault-free result), p50/
p95 submit->result latency, and the fault counters (injected faults,
bucket failures, retries).  Rate 0.0 is the fault-free reference row, so
``degradation_x`` is directly the chaos tax.

Each rate runs in BOTH serving modes: ``sync`` (the caller drives
``serve()`` — flush loop steps inline) and ``background`` (the
scheduler runs on the server's stepper thread; the caller submits and
blocks on ``results(ticket, timeout_s=...)``) — the two concurrency
stories the runtime supports.  The retry backoff is deliberately small
(5 ms base) so the benchmark measures scheduling overhead, not sleeps.

The ``--mesh`` section (also part of ``--json``) prices the DISTRIBUTED
rung: a mesh-sharded rollout hit by a seeded ``dist.exchange`` fault
storm that exhausts a segment's retry budget and forces a 4 -> 2
reshard-on-failure from the shard checkpoint.  It runs in this process
on its first 4 devices (four chips, or on the CPU 8 host-platform devices,
which this script requests before JAX starts)
and records the reshard-recovery tax — faulted wall clock over the
fault-free mesh run, recovery within ``REORDER_TOL``.

    PYTHONPATH=src python benchmarks/bench_chaos.py --json [--out BENCH_chaos.json]
    PYTHONPATH=src python benchmarks/bench_chaos.py          # readable table
    PYTHONPATH=src python benchmarks/bench_chaos.py --mesh   # reshard tax only
    PYTHONPATH=src python benchmarks/bench_chaos.py --smoke  # tier-1 gate

``make bench-smoke`` runs the ``--json`` form so every PR leaves a
diffable recovery-cost trajectory point in ``BENCH_chaos.json``.
"""
import argparse
import json
import os
import time

# host-platform devices for the mesh row when this runs on the CPU (no
# effect on a TPU); must be set before JAX starts
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.kernels.ref import REORDER_TOL  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

BENCH_VERSION = 2

CELL = "box2d_r1"
GRID = (48, 48)
STEPS = 4
REQUESTS = 16
MAX_BATCH = 4
RATES = (0.0, 0.2, 0.4)
SEED = 0


def _server():
    return api.StencilServer(
        api.PAPER_SUITE()[CELL], STEPS, max_batch=MAX_BATCH,
        backends=["jnp"],
        restart=api.RestartPolicy(max_failures=25, backoff_s=0.005))


def _plan(rate):
    plan = api.FaultPlan(seed=SEED)
    if rate > 0:
        # the pinned first-call fault guarantees every faulted row
        # exercises the retry ladder at least once, independent of
        # thread interleaving; the rate rule layers seeded pressure
        plan.rule("serve.settle", at=(0,))
        plan.rule("serve.settle", rate=rate)
    return plan


def _run_sync(server, states, rate):
    with _plan(rate) as plan:
        t0 = time.perf_counter()
        outs = server.serve(states)
        wall = time.perf_counter() - t0
    return outs, wall, plan


def _run_background(server, states, rate):
    server.start()
    try:
        with _plan(rate) as plan:
            t0 = time.perf_counter()
            tickets = [server.submit(s) for s in states]
            outs = [server.results(t, timeout_s=300.0) for t in tickets]
            wall = time.perf_counter() - t0
    finally:
        server.stop()
    return outs, wall, plan


def measure(rates=RATES, requests=REQUESTS):
    """One warm measured pass per (mode, rate); every row's results are
    checked against the fault-free sync baseline within REORDER_TOL."""
    rng = np.random.default_rng(3)
    states = [rng.normal(size=GRID).astype(np.float32)
              for _ in range(requests)]
    baseline = None
    out = {}
    for mode, runner in (("sync", _run_sync),
                         ("background", _run_background)):
        rows = {}
        for rate in rates:
            server = _server()
            # cold: plans + compiles — every bucket size the background
            # stepper's trickle admission can form (4, 2, 1), so no jit
            # compile pollutes the measured pass
            server.serve(states)
            server.serve(states[:2])
            server.serve(states[:1])
            server.reset_stats()
            outs, wall, plan = runner(server, states, rate)
            arr = [np.asarray(o) for o in outs]
            if baseline is None:
                baseline = arr             # sync rate-0 reference
            for a, b in zip(arr, baseline):
                # recovery recomputes the bucket, maybe at another bucket
                # size: equal to the baseline up to contraction order
                np.testing.assert_allclose(a, b, **REORDER_TOL)
            s = server.stats()
            rows[f"{rate:g}"] = {
                "wall_ms": wall * 1e3,
                "throughput_states_per_s": requests / wall,
                "p50_latency_ms": s["latency"]["p50_s"] * 1e3,
                "p95_latency_ms": s["latency"]["p95_s"] * 1e3,
                "injected": plan.fired(),
                "bucket_failures": s["faults"]["bucket_failures"],
                "retries": s["faults"]["retries"],
            }
        ref = rows[f"{rates[0]:g}"]["wall_ms"]
        for row in rows.values():
            row["degradation_x"] = row["wall_ms"] / ref
        out[mode] = rows
    return out


# The distributed rung: a 4-device mesh of this process's own devices (four
# chips, or XLA's host-platform devices on the CPU).  It runs in this
# process: a parent that has touched JAX holds the chip, so a child could
# not reach it.
_MESH_DEVICES = 4


def measure_mesh():
    """The reshard-recovery tax row: a seeded ``dist.exchange`` storm on a
    4-way mesh forces a 4 -> 2 reshard; recovery within REORDER_TOL of
    the fault-free 4-device run."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.rollout.executor import compile_program, run_checkpointed
    from repro.rollout.program import RolloutProgram, Segment, UpdateOp

    devices = jax.devices()
    if len(devices) < _MESH_DEVICES:
        raise RuntimeError(
            f"the mesh row needs {_MESH_DEVICES} devices, this process has "
            f"{len(devices)} (on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={_MESH_DEVICES})")
    spec = api.PAPER_SUITE()[CELL]
    x = jnp.asarray(np.random.default_rng(0).normal(size=GRID), jnp.float32)

    def compiled(n):
        prob = api.StencilProblem(spec, GRID, boundary="periodic", steps=1,
                                  mesh=make_mesh((n,), ("gx",),
                                                 devices=devices[:n]),
                                  grid_axes=("gx", ""))
        prog = RolloutProgram(prob, [
            Segment(2, emit=True),
            Segment(2, UpdateOp("scale", {"factor": 0.5}), emit=True),
            Segment(2, emit=True)])
        return compile_program(prog, backends=["jnp"])

    def timed(fn, reps=3):
        best, out = None, None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best, out

    def checkpointed(n):
        # fault-free rows checkpoint too, so the tax ratio isolates the
        # retries + reshard recompile + resharded restore, not the writes
        with tempfile.TemporaryDirectory() as d:
            return run_checkpointed(compiled(n), x, directory=d)

    compiled(4), compiled(2)                  # warm the compiles
    free_s, ref = timed(lambda: checkpointed(4))
    shrunk_s, _ = timed(lambda: checkpointed(2))

    def faulted():
        with tempfile.TemporaryDirectory() as d:
            plan = api.FaultPlan(seed=5).rule("dist.exchange", at=(1, 2, 3),
                                              match={"chunk": 0})
            with plan:
                res = run_checkpointed(
                    compiled(4), x, directory=d,
                    restart=api.RestartPolicy(max_failures=2, backoff_s=0.0))
            return plan, res

    fault_s, (plan, res) = timed(faulted)
    assert res.resharded == 1 and res.recovered == (0, 1, 0), (
        res.resharded, res.recovered)
    for (_, a), (_, b) in zip(res.emits, ref.emits):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **REORDER_TOL)
    return {
        "mesh_shape": [4], "shrunk_shape": [2], "grid": list(GRID),
        "site": "dist.exchange", "injected": plan.fired(),
        "attempts": list(res.attempts), "resharded": res.resharded,
        "fault_free_ms": free_s * 1e3,
        "shrunk_fault_free_ms": shrunk_s * 1e3,
        "faulted_ms": fault_s * 1e3,
        "reshard_tax_x": fault_s / free_s,
        "within_tol": True,
    }


def emit_json(path="BENCH_chaos.json"):
    data = {
        "bench_version": BENCH_VERSION,
        "cell": CELL, "grid": list(GRID), "steps": STEPS,
        "requests": REQUESTS, "max_batch": MAX_BATCH,
        "fault_site": "serve.settle", "seed": SEED,
        "rates": [f"{r:g}" for r in RATES],
        "measured": measure(),
        "mesh": measure_mesh(),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    m = data["measured"]
    worst = max(r["degradation_x"] for rows in m.values()
                for r in rows.values())
    print(f"wrote {path}: {len(RATES)} fault rates x "
          f"{len(m)} modes, all recoveries within tolerance; worst-case "
          f"chaos tax {worst:.2f}x wall clock; mesh reshard tax "
          f"{data['mesh']['reshard_tax_x']:.2f}x")
    return data


def table():
    print("mode,rate,wall_ms,states_per_s,p95_ms,injected,retries,"
          "degradation_x")
    for mode, rows in measure().items():
        for rate, r in rows.items():
            print(f"{mode},{rate},{r['wall_ms']:.1f},"
                  f"{r['throughput_states_per_s']:.1f},"
                  f"{r['p95_latency_ms']:.2f},{r['injected']},"
                  f"{r['retries']},{r['degradation_x']:.2f}")


def smoke():
    """Tiny tier-1 pass: one faulted rate per mode, recovery within
    REORDER_TOL of the fault-free pass."""
    m = measure(rates=(0.0, 0.3), requests=6)
    for mode in ("sync", "background"):
        faulted = m[mode]["0.3"]
        assert faulted["injected"] > 0, m
        assert faulted["retries"] == faulted["bucket_failures"], m
        print(f"{mode}: rate 0.3 -> {faulted['injected']} faults, "
              f"{faulted['retries']} retries, "
              f"{faulted['throughput_states_per_s']:.1f} states/s "
              f"(tax {faulted['degradation_x']:.2f}x), all recovered")
    print("bench-chaos smoke OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_chaos.json")
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny faulted pass per mode (the tier-1 gate)")
    ap.add_argument("--mesh", action="store_true",
                    help="only the distributed reshard-recovery tax row "
                         "(needs 4 devices in this process)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        smoke()
        return
    if args.mesh:
        row = measure_mesh()
        print(json.dumps(row, indent=2, sort_keys=True))
        print(f"reshard 4 -> 2 recovery tax "
              f"{row['reshard_tax_x']:.2f}x (within tolerance)")
        return
    if args.json:
        emit_json(args.out)
        return
    table()


if __name__ == "__main__":
    main()
