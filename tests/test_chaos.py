"""Chaos-hardened serving runtime: deterministic fault injection, the
retry/fallback/evict/shed degradation ladder, the thread-safe background
stepper, corrupt-checkpoint resume, and the bit-exactness acceptance
gates (faulted server == fault-free sync server).  The exhaustive
site x rate matrix is slow-marked; one seeded smoke scenario is tier-1."""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import api
from repro.checkpoint.checkpointer import restore_checkpoint, retained_steps
from repro.core import stencil_spec as ss
from repro.kernels.ref import REORDER_TOL, stencil_ref
from repro.runtime import chaos

from test_multidevice import run_with_devices


def _ref(state, spec, steps, boundary="periodic"):
    out = jnp.asarray(state)
    for _ in range(steps):
        out = stencil_ref(out, spec, boundary=boundary)
    return np.asarray(out)


def _quick_restart(**kw):
    cfg = dict(max_failures=8, backoff_s=0.005)
    cfg.update(kw)
    return api.RestartPolicy(**cfg)


# ---------------------------------------------------------------------------
# The harness itself
# ---------------------------------------------------------------------------

def test_fault_plan_fires_deterministically_per_seed():
    def pattern(seed):
        plan = chaos.FaultPlan(seed=seed).rule("serve.settle", rate=0.4)
        out = []
        with plan:
            for _ in range(32):
                try:
                    chaos.fire("serve.settle", shape="16x16", device=0)
                    out.append(0)
                except chaos.FaultError:
                    out.append(1)
        return out, plan

    p7a, plan = pattern(7)
    p7b, _ = pattern(7)
    assert p7a == p7b                       # same seed -> same fire indices
    assert p7a != pattern(8)[0]             # a different seeded stream
    assert 0 < sum(p7a) < 32                # rate actually samples
    assert plan.fired() == sum(p7a) == plan.fired("serve.settle")
    assert plan.calls("serve.settle") == 32
    # the log records (site, per-rule call index, action, ctx)
    site, idx, action, ctx = plan.log[0]
    assert site == "serve.settle" and action == "raise"
    assert ctx == {"shape": "16x16", "device": 0}
    assert plan.stats()["by_site"] == {"serve.settle": plan.fired()}


def test_fault_rule_at_times_match_and_actions():
    plan = (chaos.FaultPlan(seed=0)
            .rule("serve.dispatch", at=(1, 3), match={"device": 1})
            .rule("cache.compile", rate=1.0, times=2)
            .rule("checkpoint.write", at=(0,), action="corrupt")
            .rule("serve.settle", at=(0,), action="delay", delay_s=0.01))
    with plan:
        # match= filters on ctx: device=0 calls are not even counted
        for _ in range(5):
            chaos.fire("serve.dispatch", device=0)
        hits = 0
        for i in range(5):
            try:
                chaos.fire("serve.dispatch", device=1)
            except chaos.FaultError as e:
                assert e.site == "serve.dispatch" and e.index == i
                hits += 1
        assert hits == 2                    # exactly the pinned indices
        # times= caps a rate-1.0 rule at two fires
        fired = 0
        for _ in range(5):
            try:
                chaos.fire("cache.compile", backend="jnp")
            except chaos.FaultError:
                fired += 1
        assert fired == 2
        # corrupt returns the action string for the call site to implement
        assert chaos.fire("checkpoint.write", step=1) == "corrupt"
        assert chaos.fire("checkpoint.write", step=2) is None
        # delay sleeps and returns None
        t0 = time.perf_counter()
        assert chaos.fire("serve.settle") is None
        assert time.perf_counter() - t0 >= 0.01


def test_fault_plan_validation_and_activation():
    with pytest.raises(ValueError, match="unknown fault site"):
        chaos.FaultPlan().rule("serve.nonsense")
    with pytest.raises(ValueError, match="action"):
        chaos.FaultPlan().rule("serve.settle", action="explode")
    with pytest.raises(ValueError, match="rate"):
        chaos.FaultPlan().rule("serve.settle", rate=1.5)
    # no plan active: the hook is a no-op
    assert chaos.active() is None
    assert chaos.fire("serve.settle", device=0) is None
    plan = chaos.FaultPlan().rule("serve.settle", rate=1.0)
    with plan:
        assert chaos.active() is plan
        with pytest.raises(RuntimeError, match="already active"):
            with chaos.FaultPlan():
                pass
    assert chaos.active() is None
    # plans are also constructible from plain dicts (config-file style)
    p2 = chaos.FaultPlan(seed=3, rules=[{"site": "serve.settle",
                                         "at": (0,)}])
    with p2, pytest.raises(chaos.FaultError):
        chaos.fire("serve.settle")


def test_fault_plan_replay_round_trip():
    """``replay()`` exports the FIRED faults as a rate-0 plan pinned to
    the exact per-rule matching-call indices: replayed against the same
    call pattern it reproduces the original run's outcomes and log
    byte-for-byte, even with several interleaved match-filtered rules —
    and replaying a replay is a fixed point."""
    plan = (chaos.FaultPlan(seed=11)
            .rule("serve.settle", rate=0.4, match={"device": 0})
            .rule("serve.dispatch", rate=0.3, action="corrupt"))

    def drive(p):
        outcomes = []
        with p:
            for i in range(24):
                for site in ("serve.dispatch", "serve.settle"):
                    try:
                        out = chaos.fire(site, shape="16x16", device=i % 2)
                        outcomes.append((site, i, out))
                    except chaos.FaultError:
                        outcomes.append((site, i, "raise"))
        return outcomes

    o1 = drive(plan)
    assert plan.fired("serve.settle") > 0
    assert plan.fired("serve.dispatch") > 0
    rp = plan.replay()
    assert all(r.rate == 0.0 and r.times is None for r in rp._rules)
    assert [(r.site, r.action, r.match) for r in rp._rules] == \
        [(r.site, r.action, r.match) for r in plan._rules]
    assert drive(rp) == o1
    assert rp.log == plan.log
    rp2 = rp.replay()
    assert [r.at for r in rp2._rules] == [r.at for r in rp._rules]


# ---------------------------------------------------------------------------
# Acceptance: bit-exact recovery under seeded dispatch/compile/settle faults
# ---------------------------------------------------------------------------

def _mixed_stream(rng, n=7):
    shapes = [(32, 32), (24, 24)]
    return [rng.normal(size=shapes[i % 2]).astype(np.float32)
            for i in range(n)]


def test_serve_bit_exact_under_seeded_fault_plan():
    """The acceptance gate: with a seeded FaultPlan injecting dispatch,
    compile and settle faults, every request still returns results
    BIT-identical to the fault-free synchronous server."""
    spec = ss.star(2, 2, seed=1)
    rng = np.random.default_rng(11)
    states = _mixed_stream(rng)
    baseline = api.StencilServer(spec, 3, max_batch=4, backends=["jnp"],
                                 async_dispatch=False).serve(states)
    server = api.StencilServer(spec, 3, max_batch=4, backends=["jnp"],
                               restart=_quick_restart())
    plan = (api.FaultPlan(seed=2)
            .rule("serve.dispatch", rate=0.3)
            .rule("serve.settle", rate=0.3)
            .rule("cache.compile", rate=0.5, times=2))
    with plan:
        outs = server.serve(states)
    assert plan.fired() > 0, "the scenario must actually inject faults"
    for a, b in zip(outs, baseline):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = server.stats()
    assert s["faults"]["bucket_failures"] == plan.fired()
    # serve() succeeded, so every failure was retried within budget
    assert s["faults"]["retries"] == s["faults"]["bucket_failures"]
    assert s["requests"] == len(states)


def test_backend_fallback_degrades_group_bit_exact():
    """Persistent kernel faults demote the shape group to the jnp
    matrixized reference through the backend registry; results match the
    jnp-pinned fault-free server bit-exactly and stats() records the
    degraded mode."""
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(12)
    states = [rng.normal(size=(32, 32)).astype(np.float32)
              for _ in range(3)]
    baseline = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                                 async_dispatch=False,
                                 admission=False).serve(states)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["pallas"],
                               admission=False,
                               restart=_quick_restart(), fallback_after=2)
    plan = api.FaultPlan(seed=0).rule("cache.compile", rate=1.0,
                                      match={"backend": "pallas"})
    with plan:
        outs = server.serve(states)
    assert plan.fired() >= 2
    for a, b in zip(outs, baseline):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = server.stats()
    assert s["degraded"] == {"32x32": ["jnp"]}
    assert s["faults"]["fallbacks"] == 1
    assert s["requests"] == 3


def test_device_eviction_remaps_groups_and_readmits_on_probation():
    run_with_devices("""
        import time
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.core import stencil_spec as ss
        from repro.kernels.ref import REORDER_TOL, stencil_ref

        devices = jax.devices()
        assert len(devices) == 2
        spec = ss.box(2, 1, seed=0)
        # the cooldown must outlast the remapped group's first compile on
        # device 0, however loaded the host, or the device rejoins early
        server = api.StencilServer(
            spec, 2, max_batch=4, backends=["jnp"], devices=devices,
            restart=api.RestartPolicy(max_failures=6, backoff_s=0.005),
            evict_after=2, evict_cooldown_s=1.0)
        rng = np.random.default_rng(0)
        shapes = [(16, 16), (24, 24)]   # two groups -> devices 0 and 1
        states = [rng.normal(size=shapes[i % 2]).astype(np.float32)
                  for i in range(4)]
        plan = api.FaultPlan(seed=0).rule("serve.settle", rate=1.0,
                                          match={"device": 1})
        with plan:
            outs = server.serve(states)
            for state, out in zip(states, outs):
                ref = jnp.asarray(state)
                for _ in range(2):
                    ref = stencil_ref(ref, spec, boundary="periodic")
                assert float(jnp.abs(out - ref).max()) < 1e-4
            s = server.stats()
            assert s["faults"]["evictions"] == 1
            assert s["devices"][1]["evicted"]
            assert s["devices"][1]["failures"] == 2
            # the evicted device's group now runs on device 0
            assert s["devices"][0]["batches"] >= 2
            # cooldown expires -> probation re-admission takes the group
            # back; the still-injected fault is ONE strike -> re-evicted
            time.sleep(1.1)
            more = [rng.normal(size=(24, 24)).astype(np.float32)
                    for _ in range(2)]
            outs2 = server.serve(more)
            for state, out in zip(more, outs2):
                ref = jnp.asarray(state)
                for _ in range(2):
                    ref = stencil_ref(ref, spec, boundary="periodic")
                assert float(jnp.abs(out - ref).max()) < 1e-4
            s2 = server.stats()
            assert s2["faults"]["evictions"] == 2
            assert s2["devices"][1]["evicted"]
        print("EVICTION LADDER OK")
    """, n=2)


# ---------------------------------------------------------------------------
# Background stepper: thread-safe submit/results under faults
# ---------------------------------------------------------------------------

def test_background_stepper_serves_concurrent_submitters_bit_exact():
    """Acceptance gate 2: background-stepper mode with concurrent
    submitter threads, under injected settle faults, returns the results
    of the fault-free synchronous server.  Up to contraction order
    (REORDER_TOL): thread interleaving decides the bucket sizes, and a
    batched contraction of another size may sum in another order."""
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(13)
    per_thread = [[rng.normal(size=(24, 24)).astype(np.float32)
                   for _ in range(4)] for _ in range(3)]
    flat = [s for group in per_thread for s in group]
    baseline = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                                 async_dispatch=False).serve(flat)
    expect = {id(s): b for s, b in zip(flat, baseline)}

    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                               restart=_quick_restart(max_failures=12))
    got, errors = {}, []

    def submitter(states):
        try:
            tickets = [(server.submit(s), s) for s in states]
            for t, s in tickets:
                got[id(s)] = np.asarray(server.results(t, timeout_s=120.0))
        except Exception as e:              # pragma: no cover - fail loud
            errors.append(e)

    # the pinned first-call fault guarantees the scenario injects at
    # least once regardless of thread interleaving; the rate rule layers
    # seeded pressure on top
    plan = (api.FaultPlan(seed=5)
            .rule("serve.settle", at=(0,))
            .rule("serve.settle", rate=0.25))
    server.start()
    try:
        with plan:
            threads = [threading.Thread(target=submitter, args=(g,))
                       for g in per_thread]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180.0)
    finally:
        server.stop()
    assert not errors, errors
    assert not server.running
    assert plan.fired() > 0
    assert len(got) == len(flat)
    for key, out in got.items():
        np.testing.assert_allclose(out, np.asarray(expect[key]),
                                   **REORDER_TOL)
    assert server.stats()["faults"]["retries"] > 0


def test_background_stepper_blocking_results_and_restart():
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, backends=["jnp"])
    x = np.random.default_rng(1).normal(size=(16, 16)).astype(np.float32)
    server.start()
    assert server.start() is server          # idempotent
    try:
        t = server.submit(x)
        out = server.results(t, timeout_s=60.0)
        np.testing.assert_allclose(np.asarray(out), _ref(x, spec, 2),
                                   atol=1e-4)
        with pytest.raises(TimeoutError):
            t2 = server.submit(np.zeros((640, 640), np.float32))
            server.results(t2, timeout_s=1e-4)
        server.results(t2, timeout_s=60.0)   # settles fine after
    finally:
        server.stop()
    server.stop()                            # idempotent
    # stopped server still serves synchronously
    assert len(server.serve([x])) == 1


# ---------------------------------------------------------------------------
# Deadline clock across requeue + load shedding
# ---------------------------------------------------------------------------

def test_requeued_bucket_keeps_original_submit_clock():
    """Satellite: a request whose bucket fails and retries keeps its
    ORIGINAL submit time for deadline accounting — the retry backoff
    pushes it past its deadline even though the retry itself is fast."""
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(14)
    server = api.StencilServer(
        spec, 2, max_batch=4, backends=["jnp"],
        restart=api.RestartPolicy(max_failures=3, backoff_s=0.4))
    server.serve([rng.normal(size=(16, 16)).astype(np.float32)])  # warm
    server.reset_stats()
    x = rng.normal(size=(16, 16)).astype(np.float32)
    t = server.submit(x, deadline_s=0.15)
    plan = api.FaultPlan(seed=0).rule("serve.settle", at=(0,))
    with plan:
        out = server.flush()
    assert plan.fired() == 1
    np.testing.assert_allclose(np.asarray(out[t]),
                               _ref(x, spec, 2), atol=1e-4)
    s = server.stats()
    # warm retry wall clock << 0.15s: only the preserved submit clock
    # (0.4s backoff elapsed) can explain the recorded miss
    assert s["deadline_misses"] == 1
    assert s["latency"]["max_s"] >= 0.4


def test_shed_drops_lowest_priority_class_under_deadline_pressure():
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(15)
    mk = lambda: rng.normal(size=(16, 16)).astype(np.float32)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                               shed_miss_rate=0.4, shed_window=2)
    server.serve([mk()])                       # warm, no deadline
    # two sure misses fill the deadline window past the threshold
    server.submit(mk(), deadline_s=0.0)
    server.submit(mk(), deadline_s=0.0)
    server.flush()
    assert server.stats()["deadline_misses"] == 2
    low = [server.submit(mk(), priority=0) for _ in range(2)]
    high_states = [mk(), mk()]
    high = [server.submit(s, priority=1) for s in high_states]
    out = server.flush()
    assert sorted(out) == high                 # low-priority class shed
    for t, s in zip(high, high_states):
        np.testing.assert_allclose(np.asarray(out[t]), _ref(s, spec, 2),
                                   atol=1e-4)
    for t in low:
        with pytest.raises(api.RequestShed, match="shed"):
            server.results(t)
    s = server.stats()
    assert s["faults"]["shed"] == 2
    # a uniform-priority queue is never shed (nothing is "lowest")
    server.submit(mk(), deadline_s=0.0)
    server.submit(mk(), deadline_s=0.0)
    server.flush()
    only = [server.submit(mk()) for _ in range(2)]
    assert sorted(server.flush()) == only
    assert server.stats()["faults"]["shed"] == 2


# ---------------------------------------------------------------------------
# cancel() across rollout tickets (satellite regression)
# ---------------------------------------------------------------------------

def test_cancel_covers_rollout_tickets_with_partial_emits():
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"])
    x = np.random.default_rng(16).normal(size=(16, 16)).astype(np.float32)
    segs = [api.Segment(2, emit=True), api.Segment(2, emit=True),
            api.Segment(2)]
    t = server.submit_rollout(x, segs)
    server.step()                    # dispatches segment 0
    server.step()                    # settles segment 0 -> one emit
    part = server.cancel(t)
    assert isinstance(part, list)
    assert [s for s, _ in part] == [2]
    np.testing.assert_allclose(np.asarray(part[0][1]), _ref(x, spec, 2),
                               atol=1e-4)
    # the task is gone: no leak, no further stream, nothing to flush
    with pytest.raises(KeyError):
        server.rollout_results(t)
    assert server.pending_tickets() == []
    assert server.flush() == {}
    assert server.cancel(t) is False
    # cancelling a rollout whose bucket is IN FLIGHT: settle-then-drop
    t2 = server.submit_rollout(x, [api.Segment(2, emit=True)])
    server.step()                    # in flight now
    part2 = server.cancel(t2)
    assert part2 == []               # nothing emitted yet
    assert server.flush() == {}      # result dropped at settle, not booked
    assert server.stats()["faults"]["bucket_failures"] == 0


# ---------------------------------------------------------------------------
# Corrupt-latest checkpoint resume (satellite regression)
# ---------------------------------------------------------------------------

def _rollout_fixture():
    suite = api.PAPER_SUITE()
    prob = api.StencilProblem(suite["box2d_r1"], (24, 24),
                              boundary="periodic", steps=2)
    program = api.RolloutProgram(prob, [api.Segment(2, emit=True),
                                        api.Segment(2), api.Segment(2)])
    compiled = api.compile_program(api.plan_program(program,
                                                   backends=["jnp"]))
    x = np.random.default_rng(17).normal(size=(24, 24)).astype(np.float32)
    return compiled, x


def test_resume_skips_torn_latest_checkpoint(tmp_path):
    """A chaos-injected torn write (completed rename, truncated manifest)
    on the LATEST checkpoint must not break resume: the walk falls back
    to the previous retained checkpoint, bit-exact vs an uninterrupted
    run."""
    compiled, x = _rollout_fixture()
    clean = compiled.run(x)
    d = str(tmp_path / "ckpt")
    plan = api.FaultPlan(seed=0).rule("checkpoint.write", at=(2,),
                                      action="corrupt")
    with plan:
        api.run_checkpointed(compiled, x, directory=d)
    assert plan.fired("checkpoint.write") == 1
    assert retained_steps(d) == [2, 4, 6]
    # the latest checkpoint really is torn: restoring it fails outright
    with pytest.raises(Exception):
        restore_checkpoint(d, 6, {"state": np.zeros((24, 24), np.float32)})
    # resume walks newest-first, skips step 6, restores step 4 and
    # re-runs only the last segment — bit-exact vs the clean run
    out = api.run_checkpointed(compiled, x, directory=d)
    np.testing.assert_array_equal(np.asarray(out.final),
                                  np.asarray(clean.final))
    assert [s for s, _ in out.emits] == [s for s, _ in clean.emits]
    for (_, a), (_, b) in zip(out.emits, clean.emits):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rollout_segment_faults_retry_through_shared_supervision(tmp_path):
    """Injected segment faults ride the same supervised() loop the
    server's retry budgets use: bounded backoff, then bit-exact
    completion (checkpoints intact throughout)."""
    compiled, x = _rollout_fixture()
    clean = compiled.run(x)
    d = str(tmp_path / "ckpt")
    plan = api.FaultPlan(seed=0).rule("rollout.segment", at=(0, 2))
    with plan:
        out = api.run_checkpointed(
            compiled, x, directory=d,
            restart=api.RestartPolicy(max_failures=3, backoff_s=0.005),
            monitor=api.HeartbeatMonitor())
    assert plan.fired("rollout.segment") == 2
    np.testing.assert_array_equal(np.asarray(out.final),
                                  np.asarray(clean.final))
    # an exhausted budget propagates (and resets for the next caller)
    plan2 = api.FaultPlan(seed=0).rule("rollout.segment", rate=1.0)
    with plan2, pytest.raises(RuntimeError, match="restart budget"):
        api.run_checkpointed(
            compiled, x, directory=str(tmp_path / "ckpt2"),
            restart=api.RestartPolicy(max_failures=2, backoff_s=0.001))


# ---------------------------------------------------------------------------
# Bench smoke + the slow fault matrix
# ---------------------------------------------------------------------------

def test_bench_chaos_smoke_runs():
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "bench_chaos.py"),
         "--smoke"],
        capture_output=True, text=True, env=env, timeout=420)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "bench-chaos smoke OK" in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("site", ["serve.dispatch", "serve.settle",
                                  "cache.compile"])
@pytest.mark.parametrize("rate", [0.2, 0.5])
@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_fault_matrix_bit_exact(site, rate, seed):
    """The exhaustive sweep: every instrumented serving site, two fault
    rates, two seeds — recovery is always bit-exact vs the fault-free
    synchronous server."""
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(100 + seed)
    states = _mixed_stream(rng, n=6)
    baseline = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                                 async_dispatch=False).serve(states)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                               restart=_quick_restart(max_failures=20))
    plan = api.FaultPlan(seed=seed).rule(site, rate=rate)
    with plan:
        outs = server.serve(states)
    for a, b in zip(outs, baseline):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = server.stats()
    assert s["requests"] == len(states)
    assert s["faults"]["bucket_failures"] == plan.fired()
