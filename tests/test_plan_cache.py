"""Plan/executable cache + stencil serving loop: a second identical
request is a counter-visible hit with ZERO re-planning and ZERO
re-tracing; the serving loop buckets variable-size streams into padded
batches whose results match the per-state reference exactly."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import plan_cache as pc_mod
from repro.core import stencil_spec as ss
from repro.core.plan_cache import PlanCache, cache_key
from repro.kernels.ref import stencil_ref


def _problem(grid=(32, 32), steps=3, batch=1, **kw):
    return api.StencilProblem(ss.box(2, 1, seed=0), grid,
                              boundary="periodic", steps=steps,
                              batch=batch, **kw)


# ---------------------------------------------------------------------------
# Cache keying
# ---------------------------------------------------------------------------

def test_cache_key_separates_everything_that_changes_the_executable():
    base = cache_key(_problem())
    assert cache_key(_problem()) == base                     # deterministic
    assert cache_key(_problem(grid=(48, 48))) != base        # shape
    assert cache_key(_problem(steps=5)) != base              # steps
    assert cache_key(_problem(batch=4)) != base              # batch bucket
    assert cache_key(_problem(dtype="bfloat16")) != base     # dtype
    assert cache_key(_problem(), fuse=2) != base             # planner pin
    assert cache_key(_problem(), backends=["jnp"]) != base   # backend pin
    assert cache_key(_problem(), fuse_strategy="inkernel") != base
    other_spec = api.StencilProblem(ss.star(2, 1, seed=0), (32, 32),
                                    boundary="periodic", steps=3)
    assert cache_key(other_spec) != base                     # operator
    # calibration participates by CONTENT digest
    rec = {"hw": "x", "compute": {"jnp": 2.0}, "traffic": {}}
    assert cache_key(_problem(), calibration=rec) != base
    assert cache_key(_problem(), calibration=rec) == \
        cache_key(_problem(), calibration=dict(rec))
    # hardware participates by PARAMETERS, not just name: a same-named
    # spec with a different roofline constant is a different executable
    import dataclasses
    from repro.launch.mesh import TPU_V5E
    assert cache_key(_problem(), hw=TPU_V5E) != base
    tweaked = dataclasses.replace(TPU_V5E, hbm_bw=TPU_V5E.hbm_bw / 2)
    assert tweaked.name == TPU_V5E.name
    assert cache_key(_problem(), hw=tweaked) != \
        cache_key(_problem(), hw=TPU_V5E)


def test_cache_key_separates_rollout_program_identity():
    """Satellite: program identity (segment lengths, update-op ids, emit
    points) participates in the key — a rollout program and a plain
    sweep with the same total step count can never collide, and neither
    can two programs differing only in a split point, an update
    parameter, or an emit flag."""
    from repro.rollout.program import RolloutProgram, Segment, UpdateOp

    def key(segments=None):
        prog = (RolloutProgram(_problem(steps=1), segments)
                if segments is not None else None)
        total = sum(s.steps for s in segments) if segments else 5
        return cache_key(_problem(steps=total), program=prog)

    base = key([Segment(2, UpdateOp("source", {"scale": 0.1})), Segment(3)])
    assert key([Segment(2, UpdateOp("source", {"scale": 0.1})),
                Segment(3)]) == base                     # deterministic
    assert key() != base                                 # plain sweep
    assert key([Segment(3, UpdateOp("source", {"scale": 0.1})),
                Segment(2)]) != base                     # split point
    assert key([Segment(2, UpdateOp("source", {"scale": 0.2})),
                Segment(3)]) != base                     # update param
    assert key([Segment(2, UpdateOp("nudge", {"gain": 0.1})),
                Segment(3)]) != base                     # update op
    assert key([Segment(2, UpdateOp("source", {"scale": 0.1})),
                Segment(3, emit=True)]) != base          # emit point
    # the pre-extracted identity tuple keys the same as the program
    prog = RolloutProgram(_problem(steps=1),
                          [Segment(2, UpdateOp("source", {"scale": 0.1})),
                           Segment(3)])
    assert cache_key(_problem(steps=5), program=prog.identity()) == base


def test_hw_key_fields_come_from_the_object_itself():
    """Satellite fix: the hardware key is derived from the hardware
    OBJECT (dataclass fields / __dict__), not a hardcoded field list —
    a model that grows a new roofline field is a new identity, and a
    non-dataclass shim keys by its own attributes."""
    import dataclasses
    from repro.launch.mesh import TPU_V5E

    @dataclasses.dataclass(frozen=True)
    class ExtendedHW(type(TPU_V5E)):
        mxu_util_derate: float = 1.0

    base_kw = dataclasses.asdict(TPU_V5E)
    full = ExtendedHW(**base_kw, mxu_util_derate=1.0)
    derated = ExtendedHW(**base_kw, mxu_util_derate=0.5)
    # two specs differing ONLY in the field this module never heard of
    assert cache_key(_problem(), hw=full) != cache_key(_problem(), hw=derated)
    assert cache_key(_problem(), hw=full) == cache_key(_problem(), hw=full)

    class DuckHW:
        def __init__(self, extra):
            self.name = "duck"
            self.peak_flops_bf16 = 1e12
            self.hbm_bw = 1e9
            self.extra = extra

    assert cache_key(_problem(), hw=DuckHW(1)) != \
        cache_key(_problem(), hw=DuckHW(2))
    assert cache_key(_problem(), hw=DuckHW(1)) == \
        cache_key(_problem(), hw=DuckHW(1))


def test_entry_accounting_sits_after_readiness():
    """Per-entry timing hooks: the first SUCCESSFUL call books compile_s,
    warm calls book wall_s, and dispatch() alone books nothing — so a
    deferred device failure between dispatch and readiness leaves the
    executable cold (test_serve_async exercises the server-level path)."""
    cache = PlanCache()
    entry = cache.get(_problem(steps=1), backends=["jnp"])
    assert entry.calls == 0 and not entry.warm
    x = jnp.ones((32, 32), jnp.float32)
    entry(x)
    assert entry.calls == 1 and entry.warm
    assert entry.compile_s > 0 and entry.wall_s == 0.0
    entry(x)
    assert entry.calls == 2 and entry.wall_s > 0
    # dispatch() books nothing until the caller confirms readiness
    wall_before = entry.wall_s
    out = entry.dispatch(x)
    assert entry.calls == 2 and entry.wall_s == wall_before
    out.block_until_ready()
    assert entry.mark_ready(0.25) is True   # was already warm
    assert entry.calls == 3 and entry.wall_s >= wall_before + 0.25


def test_plan_only_memo_is_reused_by_get(monkeypatch):
    """A model-only query (the admission bucket-cliff walk) plans each
    bucket exactly once, and a later compiling get() of the same key
    reuses the memoized plan instead of re-planning."""
    cache = PlanCache()
    plans = []
    real_plan = pc_mod.plan
    monkeypatch.setattr(pc_mod, "plan",
                        lambda *a, **k: plans.append(1) or real_plan(*a, **k))
    p2 = cache.plan_only(_problem(batch=2), backends=["jnp"])
    assert len(plans) == 1
    assert cache.plan_only(_problem(batch=2), backends=["jnp"]) is p2
    assert len(plans) == 1
    assert cache.stats()["plans"] == 1
    entry = cache.get(_problem(batch=2), backends=["jnp"])
    assert entry.plan is p2 and len(plans) == 1, \
        "compiling miss re-planned a memoized key"
    assert cache.misses == 1 and cache.hits == 0
    assert cache.stats()["plans"] == 0    # promoted out of the memo
    # the cap walk is fully memoized on repeat
    cap = cache.bucket_cap(_problem(), 4, backends=["jnp"])
    assert 1 <= cap <= 4
    n = len(plans)
    assert cache.bucket_cap(_problem(), 4, backends=["jnp"]) == cap
    assert len(plans) == n
    # plan_only on an already-compiled entry reads the entry, no memo
    assert cache.plan_only(_problem(batch=2), backends=["jnp"]) is p2
    assert len(plans) == n


def test_second_identical_request_hits_no_replan_no_retrace(monkeypatch):
    cache = PlanCache()
    plans = []
    real_plan = pc_mod.plan
    monkeypatch.setattr(pc_mod, "plan",
                        lambda *a, **k: plans.append(1) or real_plan(*a, **k))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 32)),
                    jnp.float32)
    e1 = cache.get(_problem(), backends=["jnp"])
    out1 = e1.fn(x)
    e2 = cache.get(_problem(), backends=["jnp"])
    out2 = e2.fn(x)
    assert e2 is e1
    assert cache.hits == 1 and cache.misses == 1
    assert len(plans) == 1, "second identical request re-planned"
    assert e1.fn._cache_size() == 1, "second identical request re-traced"
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # the entry's own hit counter tracks serving reuse
    assert e1.hits == 1
    assert cache.stats()["hits"] == 1


def test_cache_lru_eviction_is_bounded():
    cache = PlanCache(maxsize=2)
    p1, p2, p3 = _problem(), _problem(steps=4), _problem(steps=5)
    e1 = cache.get(p1, backends=["jnp"])
    cache.get(p2, backends=["jnp"])
    cache.get(p3, backends=["jnp"])          # evicts p1 (LRU)
    assert len(cache) == 2 and cache.evictions == 1
    assert e1.key not in cache
    cache.get(p2, backends=["jnp"])          # still resident
    assert cache.hits == 1
    cache.get(p1, backends=["jnp"])          # must recompile
    assert cache.misses == 4


def test_cached_executables_compute_the_right_thing():
    cache = PlanCache()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 32, 32)),
                    jnp.float32)
    entry = cache.get(_problem(batch=4), backends=["jnp"])
    ref = x
    for _ in range(3):
        ref = stencil_ref(ref, _problem().spec, boundary="periodic")
    np.testing.assert_allclose(np.asarray(entry(x)), np.asarray(ref),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Serving loop
# ---------------------------------------------------------------------------

def test_serve_variable_size_stream_matches_reference():
    spec = ss.star(2, 2, seed=1)
    server = api.StencilServer(spec, steps=3, max_batch=4,
                               backends=["jnp"])
    rng = np.random.default_rng(5)
    # 7 states across two shapes, interleaved arrival
    shapes = [(32, 32), (24, 24), (32, 32), (32, 32), (24, 24), (32, 32),
              (32, 32)]
    states = [rng.normal(size=s).astype(np.float32) for s in shapes]
    outs = server.serve(states)
    for state, out in zip(states, outs):
        ref = jnp.asarray(state)
        for _ in range(3):
            ref = stencil_ref(ref, spec, boundary="periodic")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)
    s = server.stats()
    # (32,32) x5 -> bucket 4 + bucket 1; (24,24) x2 -> bucket 2: the
    # padded slots are the bucket round-up only
    assert s["requests"] == 7 and s["batches"] == 3
    assert s["padded_states"] == 0
    assert s["plan_cache"]["misses"] == 3
    # every bucket's first call is compile-accounted
    compile_wall_s = s["compile_wall_s"]
    assert compile_wall_s > 0
    server.serve(states)   # warm pass: served, and no further compile time
    s = server.stats()
    assert s["requests"] == 14 and s["batches"] == 6
    assert s["compile_wall_s"] == compile_wall_s


def test_serve_repeat_traffic_is_all_cache_hits():
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, steps=2, max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(9)
    states = [rng.normal(size=(24, 24)).astype(np.float32)
              for _ in range(4)]
    server.serve(states)
    misses_after_cold = server.cache.misses
    server.serve(states)
    server.serve(states)
    assert server.cache.misses == misses_after_cold
    assert server.cache.hits == 2
    # padded bucket: 3 states -> bucket 4, one zero state padded in
    server.serve(states[:3])
    assert server.stats()["padded_states"] == 1
    assert server.cache.misses == misses_after_cold  # same bucket reused


def test_serve_bucket_padding_never_leaks_into_results():
    """A padded (all-zero) slot must not alter real states' outputs."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, steps=2, max_batch=8, backends=["jnp"])
    rng = np.random.default_rng(3)
    states = [rng.normal(size=(24, 24)).astype(np.float32)
              for _ in range(5)]                      # bucket 8, 3 padded
    outs = server.serve(states)
    solo = api.StencilServer(spec, steps=2, max_batch=1, backends=["jnp"])
    for a, b in zip(outs, solo.serve(states)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_flush_failure_loses_no_requests_and_no_results():
    """A failing bucket must not drop other requests OR completed work:
    the failed bucket's tickets stay queued (cancel-able), buckets that
    already ran are neither recomputed nor double-counted, and their
    results surface from the next successful flush."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, steps=4, boundary="valid",
                               max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(7)
    good = [server.submit(rng.normal(size=(32, 32)).astype(np.float32))
            for _ in range(3)]
    # infeasible AND sorting after (32, 32), so the good bucket runs first
    bad = server.submit(np.ones((33, 1), np.float32))
    with pytest.raises(ValueError, match=str(bad)):
        server.flush()
    # good bucket completed and left the queue; only the bad ticket waits
    assert server.pending_tickets() == [bad]
    batches_after_fail = server.stats_.batches
    assert server.cancel(bad) and not server.cancel(bad)
    results = server.flush()
    assert sorted(results) == good, "completed results were lost"
    assert server.stats_.batches == batches_after_fail, \
        "completed bucket was recomputed after the failure"
    # and the failed bucket never polluted the serving counters
    assert server.stats_.requests == 3


def test_distributed_batched_plan_rejects_bad_input_shapes():
    """compile() of a distributed batched plan fails with the same clear
    shape errors as the single-device path (not a shard_map rank error).
    Single-device compile: exercised here; the distributed stepper itself
    is subprocess-tested in test_multidevice."""
    prob = _problem(batch=3, steps=2)
    run = api.compile(api.plan(prob, backends=["jnp"]))
    with pytest.raises(ValueError, match="batch"):
        run(jnp.ones((32, 32), jnp.float32))
    with pytest.raises(ValueError, match="batch"):
        run(jnp.ones((2, 32, 32), jnp.float32))


def test_server_validates_input_rank_and_steps():
    spec = ss.box(2, 1, seed=0)
    with pytest.raises(ValueError):
        api.StencilServer(spec, steps=-1)
    server = api.StencilServer(spec, steps=2)
    with pytest.raises(ValueError):
        server.submit(np.zeros((2, 16, 16), np.float32))  # batched submit
