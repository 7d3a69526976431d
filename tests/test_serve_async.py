"""Async continuous-batching server: bit-exactness vs the synchronous
loop, step()-driven bucket formation, latency/deadline tracking,
admission control at the batch-scaled VMEM cliff, deferred-device-error
recovery (cold-executable accounting), and multi-device routing."""
import collections
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import stencil_spec as ss
from repro.core.plan_cache import CachedExecutable, PlanCache
from repro.launch import serve_stencil
from repro.kernels.ref import stencil_ref

from test_multidevice import run_with_devices


def _ref(state, spec, steps, boundary="periodic"):
    out = jnp.asarray(state)
    for _ in range(steps):
        out = stencil_ref(out, spec, boundary=boundary)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# Dispatch-mode equivalence
# ---------------------------------------------------------------------------

def test_async_dispatch_bit_exact_vs_sync_on_mixed_stream():
    """The overlapped scheduler is a pure reordering of host work: on the
    same mixed-shape stream it forms the same buckets and returns
    BIT-identical results to the synchronous loop (and both match the
    sequential reference)."""
    spec = ss.star(2, 2, seed=1)
    rng = np.random.default_rng(5)
    shapes = [(32, 32), (24, 24), (32, 32), (32, 32), (24, 24), (32, 32),
              (32, 32)]
    states = [rng.normal(size=s).astype(np.float32) for s in shapes]
    a = api.StencilServer(spec, 3, max_batch=4, backends=["jnp"],
                          async_dispatch=True)
    s_ = api.StencilServer(spec, 3, max_batch=4, backends=["jnp"],
                           async_dispatch=False)
    outs_a, outs_s = a.serve(states), s_.serve(states)
    for state, oa, os_ in zip(states, outs_a, outs_s):
        np.testing.assert_array_equal(np.asarray(oa), np.asarray(os_))
        np.testing.assert_allclose(np.asarray(oa), _ref(state, spec, 3),
                                   atol=1e-4)
    # identical bucket formation, counters and cache traffic
    for srv in (a, s_):
        st = srv.stats()
        assert st["requests"] == 7 and st["batches"] == 3
        assert st["padded_states"] == 0
        assert st["plan_cache"]["misses"] == 3
        assert st["latency"]["count"] == 7


@pytest.mark.parametrize("kind,n,b", [
    ("plain", 1, 1), ("plain", 2, 2), ("plain", 3, 4), ("plain", 4, 4),
    ("rollout", 3, 4)],
    ids=["b1", "b2", "b4-of-3", "b4", "rollout-b4-of-3"])
def test_compiled_stack_and_split_bit_exact_vs_eager(kind, n, b):
    """One bucket of n states, stacked and split by compiled calls: the
    served results equal, bit for bit, the cache entry's own executable
    run on the eagerly stacked, zero-padded batch and then sliced per
    state; they match the per-state reference; no padded slot reaches a
    result."""
    spec = ss.star(2, 2, seed=1)
    steps, shape = 3, (24, 24)
    server = api.StencilServer(spec, steps, max_batch=4, backends=["jnp"],
                               admission=False)
    rng = np.random.default_rng(10 + n)
    states = [rng.normal(size=shape).astype(np.float32) for _ in range(n)]
    seg = api.Segment(steps, emit=True) if kind == "rollout" else None
    tickets = [server.submit(s) if seg is None
               else server.submit_rollout(s, [seg]) for s in states]
    out = server.flush()
    assert sorted(out) == tickets          # n results: none for a pad
    st = server.stats()
    assert st["batches"] == 1 and st["padded_states"] == b - n
    # the entry the server ran (a hit, no new miss) on the eager batch
    misses = server.cache.stats()["misses"]
    kw = server._plan_kwargs(shape)
    if seg is None:
        entry = server.cache.get(server._problem(shape, b), **kw)
    else:
        entry = server.cache.get_program(api.RolloutProgram(
            server._problem(shape, b, steps=steps), (seg,)), **kw)
    assert server.cache.stats()["misses"] == misses
    batch = jnp.stack([jnp.asarray(s) for s in states]
                      + [jnp.zeros(shape, jnp.float32)] * (b - n))
    ran = entry.dispatch(batch[0] if b == 1 else batch)
    final = ran if seg is None else ran[0]
    for i, (t, state) in enumerate(zip(tickets, states)):
        want = np.asarray(final if b == 1 else final[i])
        np.testing.assert_array_equal(np.asarray(out[t]), want)
        np.testing.assert_allclose(np.asarray(out[t]),
                                   _ref(state, spec, steps), atol=1e-4)
        if seg is not None:
            (at, emitted), = server.rollout_results(t)
            assert at == steps
            np.testing.assert_array_equal(np.asarray(emitted), want)


def test_dispatches_count_compiled_calls_and_warm_pass_compiles_nothing(
        monkeypatch):
    """On a mixed stream every bucket of b > 1 makes three compiled
    calls (stack, sweep, split) and a lone state one (the sweep), as
    ``stats()["dispatches"]`` books; the stack and split programs are
    cached per (shape, bucket, live count), so a second pass over the
    same shapes compiles nothing."""
    made = collections.Counter()
    for name in ("_stack", "_split"):
        def counted(*args, _fn=getattr(serve_stencil, name), _name=name):
            made[_name] += 1
            return _fn(*args)
        monkeypatch.setattr(serve_stencil, name, counted)
    sweep = CachedExecutable.dispatch

    def dispatch(self, x):
        made["sweep"] += 1
        return sweep(self, x)
    monkeypatch.setattr(CachedExecutable, "dispatch", dispatch)
    compiles = collections.Counter()

    def on_event(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            compiles[event] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                               admission=False)
    rng = np.random.default_rng(12)
    # buckets: 18x18 of 4 (one pad), 22x22 alone, 26x26 of 2, and 20x20
    # of 4 then alone
    shapes = [(18, 18)] * 3 + [(22, 22)] + [(26, 26)] * 2 + [(20, 20)] * 5
    states = [rng.normal(size=s).astype(np.float32) for s in shapes]
    try:
        for attempt in range(2):
            server.reset_stats()
            made.clear()
            compiles.clear()
            outs = server.serve(states)
            st = server.stats()
            assert st["batches"] == 5 and st["padded_states"] == 1
            assert made == {"_stack": 3, "sweep": 5, "_split": 3}
            assert st["dispatches"] == 3 * 3 + 2 == sum(made.values())
            if attempt == 0:
                assert compiles, "the first pass compiles its buckets"
            else:
                assert not compiles, compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    for state, out in zip(states, outs):
        np.testing.assert_allclose(np.asarray(out), _ref(state, spec, 2),
                                   atol=1e-4)


def test_step_admits_newly_submitted_states_between_turns():
    """Continuous batching: a state submitted while a bucket is in flight
    rides the NEXT turn's bucket — two singleton buckets, not one of 2 —
    and results flow through ready()/results()."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(4)
    s0 = rng.normal(size=(16, 16)).astype(np.float32)
    s1 = rng.normal(size=(16, 16)).astype(np.float32)
    t0 = server.submit(s0)
    assert server.step() == 0            # dispatched, still in flight
    t1 = server.submit(s1)               # admitted into the next turn
    assert server.step() == 1            # settles t0, dispatches t1
    assert server.ready(t0) and not server.ready(t1)
    assert server.step() == 1
    np.testing.assert_allclose(np.asarray(server.results(t0)),
                               _ref(s0, spec, 2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(server.results(t1)),
                               _ref(s1, spec, 2), atol=1e-4)
    assert server.stats()["batches"] == 2
    with pytest.raises(KeyError, match="no claimable result"):
        server.results(t0)               # already claimed
    with pytest.raises(KeyError):
        server.results(999)              # never existed


def test_latency_and_deadline_tracking():
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(2)
    states = [rng.normal(size=(16, 16)).astype(np.float32)
              for _ in range(3)]
    server.submit(states[0], deadline_s=0.0)    # every latency > 0: a miss
    server.submit(states[1], deadline_s=1e6)    # never missed
    server.submit(states[2])                    # no deadline: never a miss
    server.flush()
    s = server.stats()
    assert s["deadline_misses"] == 1
    lat = s["latency"]
    assert lat["count"] == 3
    assert 0 < lat["p50_s"] <= lat["p95_s"] <= lat["max_s"]
    assert lat["mean_s"] > 0
    server.reset_stats()
    assert server.stats()["latency"]["count"] == 0


# ---------------------------------------------------------------------------
# Admission control (the batch-scaled VMEM cliff)
# ---------------------------------------------------------------------------

def test_planner_bucket_cliff_query():
    """max_profitable_batch caps the 3-D star at the model grid BELOW
    max_batch (the batch-scaled VMEM pruning makes B=8 a modelled
    per-state loss) while the 2-D box keeps winning to B=8."""
    assert api.serving_buckets(8) == [1, 2, 4, 8]
    assert api.serving_buckets(6) == [1, 2, 4, 6]
    assert api.serving_buckets(1) == [1]
    suite = api.PAPER_SUITE()
    star = api.StencilProblem(suite["star3d_r2"], (64, 64, 64),
                              boundary="periodic", steps=16)
    box = api.StencilProblem(suite["box2d_r1"], (256, 256),
                             boundary="periodic", steps=16)
    curve = api.batch_cost_curve(star, 8)
    assert set(curve) == {1, 2, 4, 8}
    cap = api.max_profitable_batch(star, 8)
    assert cap < 8, curve                  # the cliff caps the bucket
    assert curve[cap] == min(curve.values())
    assert api.max_profitable_batch(box, 8) == 8
    # rtol loosens the cap monotonically; huge rtol admits everything
    assert api.max_profitable_batch(star, 8, rtol=1e9) == 8


def test_server_admission_caps_bucket_formation(monkeypatch):
    """With the cliff query answering 2, five same-shape states form
    3 buckets (2+2+1, no padding) instead of one padded bucket of 8 —
    and the capped stream still matches the uncapped results."""
    monkeypatch.setattr(PlanCache, "bucket_cap",
                        lambda self, problem, max_batch, **kw: 2)
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(6)
    states = [rng.normal(size=(16, 16)).astype(np.float32)
              for _ in range(5)]
    capped = api.StencilServer(spec, 2, max_batch=8, backends=["jnp"])
    outs = capped.serve(states)
    s = capped.stats()
    assert s["admission"] == {"16x16": 2}
    assert s["batches"] == 3 and s["padded_states"] == 0   # 2+2+1
    assert s["plan_cache"]["misses"] == 2                  # buckets {2, 1}
    free = api.StencilServer(spec, 2, max_batch=8, backends=["jnp"],
                             admission=False)
    outs_free = free.serve(states)
    assert free.stats()["batches"] == 1
    assert free.stats()["padded_states"] == 3              # bucket of 8
    assert free.stats()["admission"] == {"16x16": 8}
    for a, b in zip(outs, outs_free):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# Failure recovery under deferred dispatch
# ---------------------------------------------------------------------------

class _Boom:
    """An unrealized 'result' whose readiness wait raises — the shape of
    a deferred device error under JAX async dispatch."""

    def block_until_ready(self):
        raise RuntimeError("deferred device error")


def test_deferred_device_failure_keeps_executable_cold_and_requeues():
    """A bucket whose device work fails AFTER dispatch: its requests are
    requeued, nothing is double-counted, and — the satellite-2 contract —
    the executable books NO successful call, so the retry's real first
    call is still accounted as compile, not warm, time."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                               admission=False)
    rng = np.random.default_rng(8)
    states = [rng.normal(size=(16, 16)).astype(np.float32)
              for _ in range(4)]
    tickets = [server.submit(s) for s in states]
    # pre-seed the bucket-4 entry and sabotage its dispatch
    entry = server.cache.get(server._problem((16, 16), 4),
                             backends=["jnp"])
    real_fn = entry.fn
    entry.fn = lambda x: _Boom()
    with pytest.raises(ValueError, match="stay queued"):
        server.flush()
    assert entry.calls == 0 and entry.compile_s == 0.0     # still COLD
    assert not entry.warm
    assert sorted(server.pending_tickets()) == tickets     # nothing lost
    assert server.stats_.batches == 0 and server.stats_.requests == 0
    assert server.stats()["latency"]["count"] == 0
    entry.fn = real_fn
    outs = server.flush()
    assert sorted(outs) == tickets
    for t, state in zip(tickets, states):
        np.testing.assert_allclose(np.asarray(outs[t]),
                                   _ref(state, spec, 2), atol=1e-4)
    # the recovery call was the entry's FIRST success: compile-accounted
    assert entry.calls == 1 and entry.compile_s > 0
    assert entry.wall_s == 0.0
    assert server.stats_.compile_wall_s > 0
    assert server.stats_.batches == 1 and server.stats_.requests == 4


def test_serve_does_not_drop_recovered_results_of_other_tickets():
    """Satellite-1 regression: results recovered by a later flush for
    tickets serve() does NOT own used to be silently discarded with the
    rest of its claim; they must stay claimable via results()/flush()."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 4, boundary="valid", max_batch=4,
                               backends=["jnp"])
    rng = np.random.default_rng(7)
    good_states = [rng.normal(size=(32, 32)).astype(np.float32)
                   for _ in range(2)]
    good = [server.submit(s) for s in good_states]
    bad = server.submit(np.ones((33, 1), np.float32))  # infeasible shape
    with pytest.raises(ValueError, match=str(bad)):
        server.flush()
    assert server.pending_tickets() == [bad]
    assert server.ready(good[0]) and server.ready(good[1])
    server.cancel(bad)
    # serve() on fresh traffic claims only its own ticket...
    outs = server.serve([rng.normal(size=(32, 32)).astype(np.float32)])
    assert len(outs) == 1
    # ...and the recovered results are still claimable afterwards
    assert server.ready(good[0]) and server.ready(good[1])
    np.testing.assert_allclose(np.asarray(server.results(good[0])),
                               _ref(good_states[0], spec, 4,
                                    boundary="valid"), atol=1e-4)
    assert list(server.flush()) == [good[1]]
    assert not server.ready(good[1])


# ---------------------------------------------------------------------------
# Multi-device routing (subprocess: fake CPU devices)
# ---------------------------------------------------------------------------

def test_multi_device_round_robin_shape_groups():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.core import stencil_spec as ss
        from repro.kernels.ref import stencil_ref

        devices = jax.devices()
        assert len(devices) == 4
        spec = ss.box(2, 1, seed=0)
        server = api.StencilServer(spec, 2, max_batch=4, backends=["jnp"],
                                   devices=devices)
        assert len(server.caches) == 4
        rng = np.random.default_rng(0)
        shapes = [(16, 16), (24, 24), (32, 32)]
        states = [rng.normal(size=shapes[i % 3]).astype(np.float32)
                  for i in range(9)]
        outs = server.serve(states)
        for state, out in zip(states, outs):
            ref = jnp.asarray(state)
            for _ in range(2):
                ref = stencil_ref(ref, spec, boundary="periodic")
            assert float(jnp.abs(out - ref).max()) < 1e-4
        s = server.stats()
        # three shape groups -> three DISTINCT devices, sticky routing
        used = [d for d in s["devices"] if d["batches"]]
        assert len(used) == 3
        assert len({d["device"] for d in used}) == 3
        for d in used:
            assert d["batches"] == 1 and d["states"] == 3
            assert d["plan_cache"]["misses"] == 1
        # merged plan-cache column sums the per-device caches
        assert s["plan_cache"]["misses"] == 3
        server.serve(states)   # warm: same groups, same devices, all hits
        s2 = server.stats()
        assert s2["plan_cache"]["misses"] == 3
        assert s2["plan_cache"]["hits"] == 3
        print("MULTI-DEVICE SERVE OK")
    """, n=4)


# ---------------------------------------------------------------------------
# Bench smoke (the serving benchmark must run end to end on a tiny cell)
# ---------------------------------------------------------------------------

def test_bench_serve_smoke_runs():
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "bench_serve.py"),
         "--smoke"],
        capture_output=True, text=True, env=env, timeout=420)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "bench-serve smoke OK" in proc.stdout
    assert "admission cap" in proc.stdout


# ---------------------------------------------------------------------------
# Accessor/scheduler races: timeout expiry mid-settle, cancel vs requeue
# ---------------------------------------------------------------------------

def test_results_timeout_expires_mid_settle_then_claims():
    """``results(ticket, timeout_s=...)`` expiring WHILE the ticket's
    bucket is still settling raises ``TimeoutError`` without consuming
    anything; a second blocking claim returns the correct result once
    the (chaos-delayed) settle lands."""
    from repro.runtime import chaos
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, 2, max_batch=2, backends=["jnp"])
    rng = np.random.default_rng(0)
    state = rng.normal(size=(16, 16)).astype(np.float32)
    server.serve([state])          # warm: the injected delay dominates
    plan = chaos.FaultPlan(seed=0).rule("serve.settle", action="delay",
                                        delay_s=0.6, at=(0,))
    server.start(poll_s=0.01)
    try:
        with plan:
            t = server.submit(state)
            with pytest.raises(TimeoutError):
                server.results(t, timeout_s=0.05)
            out = server.results(t, timeout_s=30.0)
    finally:
        server.stop()
    assert plan.fired("serve.settle") == 1
    np.testing.assert_allclose(np.asarray(out), _ref(state, spec, 2),
                               atol=1e-4)
    # the expired wait neither lost nor double-claimed the ticket
    with pytest.raises(KeyError):
        server.results(t)


def test_cancel_races_requeued_bucket():
    """A ticket cancelled while its FAILED bucket sits requeued is gone
    for good: the retry bucket re-forms without it, the survivors settle
    with correct values, and the cancelled ticket has no claimable
    result."""
    from repro.runtime import chaos
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(
        spec, 2, max_batch=4, backends=["jnp"], admission=False,
        async_dispatch=False,
        restart=api.RestartPolicy(max_failures=3, backoff_s=0.0))
    rng = np.random.default_rng(3)
    states = [rng.normal(size=(16, 16)).astype(np.float32)
              for _ in range(3)]
    tickets = [server.submit(s) for s in states]
    plan = chaos.FaultPlan(seed=0).rule("serve.settle", at=(0,))
    with plan:
        server.step()   # sync mode: dispatch + failed settle + requeue
        assert sorted(server.pending_tickets()) == sorted(tickets)
        assert server.cancel(tickets[1]) is True
        outs = server.flush()
    assert sorted(outs) == sorted([tickets[0], tickets[2]])
    for t, state in ((tickets[0], states[0]), (tickets[2], states[2])):
        np.testing.assert_allclose(np.asarray(outs[t]),
                                   _ref(state, spec, 2), atol=1e-4)
    with pytest.raises(KeyError):
        server.results(tickets[1])
    st = server.stats()
    assert st["faults"]["bucket_failures"] == 1
    assert st["faults"]["retries"] == 1
    assert st["requests"] == 2
