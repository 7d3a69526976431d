"""Rollout programs: spec/registry, per-segment planning, compiled
execution exactness, checkpointed fault-tolerant driving, serving.

The acceptance bar: a program with >=3 segments, >=2 distinct update
operators and batch B>1 matches an unfused step-by-step reference on all
three boundaries up to contraction order (``REORDER_TOL``: fused and
per-step kernels contract operands of other shapes, summed in another
order) — and a run killed mid-program resumes from its latest segment
checkpoint to the SAME bits as an uninterrupted run (the same
executables on the same state).
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import stencil_spec as ss
from repro.core.plan_cache import PlanCache, cache_key
from repro.core.planner import PLAN_VERSION, StencilProblem
from repro.kernels.ref import REORDER_TOL
from repro.launch.serve_stencil import StencilServer
from repro.rollout import (CompiledRollout, RolloutPlan, RolloutProgram,
                           RolloutResult, Segment, UpdateOp, as_segments,
                           build_update, compile_program, plan_program,
                           register_update_op, run_checkpointed,
                           update_op_names)
from repro.runtime.fault_tolerance import (HeartbeatMonitor, RestartPolicy,
                                           StepTimeout)

SUITE = ss.PAPER_SUITE()

# the acceptance program: 3 segments, 2 distinct update ops, emit points
SEGMENTS = (
    Segment(3, UpdateOp("source", {"scale": 0.1, "seed": 1}), emit=True),
    Segment(2, UpdateOp("nudge", {"gain": 0.25, "seed": 2})),
    Segment(4, emit=True),
)


def _program(spec=None, grid=(32, 32), boundary="periodic", batch=2,
             segments=SEGMENTS):
    spec = spec if spec is not None else SUITE["box2d_r1"]
    prob = StencilProblem(spec, grid, boundary=boundary, steps=1,
                          batch=batch)
    return RolloutProgram(prob, segments)


def _state(program, seed=0):
    rng = np.random.default_rng(seed)
    shape = ((program.problem.batch,) if program.problem.batch > 1
             else ()) + program.problem.grid
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _stepwise_reference(program, rplan, x):
    """The unfused step-by-step oracle: per segment, `steps` applications
    of a depth-1 plan PINNED to the segment plan's (backend, block, base
    cover) — the same-arithmetic reference of test_inkernel — then the
    segment's jitted update op."""
    valid = program.problem.boundary == "valid"
    y = x
    for i, seg in enumerate(program.segments):
        p = rplan.segment_plans[i]
        pb1 = dataclasses.replace(program.segment_problem(i), steps=1)
        one = None
        for _ in range(seg.steps):
            if valid or one is None:
                # 'valid' shrinks the grid every application: re-plan the
                # one-step reference at the current shape (test_inkernel's
                # one-ulp caveat comes exactly from this re-tiling)
                grid = tuple(y.shape[y.ndim - len(pb1.grid):])
                one = api.compile(api.plan(
                    dataclasses.replace(pb1, grid=grid),
                    backends=[p.backend], option=p.option,
                    block=tuple(min(b, g)
                                for b, g in zip(p.block, grid))))
            y = one.fn(y)
        if seg.update is not None:
            y = jax.jit(build_update(seg.update,
                                     program.segment_problem(i)))(y)
    return y


# ---------------------------------------------------------------------------
# Program spec + update-op registry
# ---------------------------------------------------------------------------

def test_program_spec_validation_and_identity():
    prog = _program()
    assert prog.total_steps == 9
    assert prog.emit_steps() == [3, 9]
    ident = prog.identity()
    assert len(ident) == 3
    assert ident[0][0] == 3 and ident[0][2] is True
    assert ident[2] == (4, None, True)
    # identity reacts to every program-shaping knob
    assert _program(segments=(Segment(3), Segment(2), Segment(4))
                    ).identity() != ident
    changed = (SEGMENTS[0], Segment(2, UpdateOp("nudge", {"gain": 0.5,
                                                          "seed": 2})),
               SEGMENTS[2])
    assert _program(segments=changed).identity() != ident
    assert _program(segments=changed).digest() != prog.digest()
    with pytest.raises(ValueError, match="segment"):
        RolloutProgram(prog.problem, ())
    with pytest.raises(ValueError):
        Segment(0)


def test_program_round_trip_and_normalization():
    prog = _program()
    back = RolloutProgram.from_dict(json.loads(json.dumps(prog.to_dict())))
    assert back.identity() == prog.identity()
    assert back.digest() == prog.digest()
    assert back.problem.grid == prog.problem.grid
    # as_segments sugar: ints, tuples, dicts
    segs = as_segments([4, (2, UpdateOp("scale", {"factor": 0.5})),
                        {"steps": 3, "emit": True}])
    assert segs[0] == Segment(4)
    assert segs[1].update.op == "scale"
    assert segs[2].emit


def test_update_op_registry_and_identity():
    assert {"source", "nudge", "scale"} <= set(update_op_names())
    a = UpdateOp("source", {"scale": 0.1, "seed": 3})
    b = UpdateOp("source", {"seed": 3, "scale": 0.1})
    assert a.update_id == b.update_id          # canonical param JSON
    assert a.update_id != UpdateOp("source", {"scale": 0.2,
                                              "seed": 3}).update_id
    with pytest.raises(ValueError, match="JSON-native"):
        UpdateOp("source", {"field": np.zeros(3)})
    with pytest.raises(ValueError, match="unknown update op"):
        build_update(UpdateOp("no_such_op"), _program().problem, (8, 8))
    # user extension point: registered ops build + execute like built-ins
    register_update_op("test_clip",
                       lambda params, pb, grid:
                       lambda x: jnp.clip(x, -params["lim"], params["lim"]),
                       overwrite=True)
    fn = build_update(UpdateOp("test_clip", {"lim": 0.5}),
                      _program().problem, (8, 8))
    out = fn(jnp.full((8, 8), 3.0))
    np.testing.assert_array_equal(np.asarray(out), np.full((8, 8), 0.5))
    with pytest.raises(ValueError, match="already registered"):
        register_update_op("test_clip", lambda *a: None)


def test_valid_boundary_grid_threading():
    # r=1, 3+2+4 steps: each segment starts from the previous shrink
    prog = _program(grid=(40, 40), boundary="valid", batch=1)
    assert prog.segment_grid(0) == (40, 40)
    assert prog.segment_grid(1) == (34, 34)   # -2*1*3
    assert prog.segment_grid(2) == (30, 30)   # -2*1*2
    from repro.rollout.program import segment_out_grid
    assert segment_out_grid(prog.segment_problem(2)) == (22, 22)
    with pytest.raises(ValueError, match="shrinks"):
        _program(grid=(12, 12), boundary="valid", batch=1)


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------

def test_plan_program_per_segment_decisions_and_round_trip():
    prog = _program()
    rp = plan_program(prog, backends=["pallas"])
    assert rp.version == PLAN_VERSION
    assert len(rp.segment_plans) == 3
    for seg, p in zip(prog.segments, rp.segment_plans):
        assert p.steps == seg.steps
        assert p.version == PLAN_VERSION
    # depths are chosen per segment (a 4-step window can fuse deeper
    # than a 2-step hop ever could)
    assert rp.segment_plans[2].fuse_depth <= 4
    assert rp.segment_plans[1].fuse_depth <= 2
    back = RolloutPlan.from_json(rp.to_json())
    assert back == rp
    text = rp.explain()
    assert "RolloutPlan v" in text and "3 segments" in text
    assert "source" in text and "nudge" in text
    t = rp.traffic()
    assert t["fused_bytes_per_state"] > 0
    assert t["traffic_ratio"] >= 1.0
    with pytest.raises(ValueError, match="version"):
        RolloutPlan.from_json(json.dumps(
            dict(json.loads(rp.to_json()), version=PLAN_VERSION - 1)))


def test_plan_program_through_cache_memo():
    cache = PlanCache()
    prog = _program()
    rp1 = plan_program(prog, cache=cache, backends=["jnp"])
    n_plans = cache.stats()["plans"]
    assert n_plans >= 1
    rp2 = plan_program(prog, cache=cache, backends=["jnp"])
    assert cache.stats()["plans"] == n_plans  # memo reuse, no regrowth
    assert rp2 == rp1


# ---------------------------------------------------------------------------
# Execution exactness (acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("boundary", ["periodic", "zero", "valid"])
def test_program_bit_exact_vs_stepwise(boundary):
    """>=3 segments, 2 distinct update ops, batch 2, pallas+inkernel:
    equal to the unfused per-step reference up to contraction order
    (REORDER_TOL: the fused and per-step kernels contract operands of
    other shapes, which XLA:CPU and the MXU sum in another order)."""
    grid = (40, 40) if boundary == "valid" else (32, 32)
    prog = _program(grid=grid, boundary=boundary)
    rp = plan_program(prog, backends=["pallas"], fuse_strategy="inkernel")
    compiled = compile_program(rp)
    x = _state(prog)
    res = compiled.run(x)
    ref = _stepwise_reference(prog, rp, x)
    np.testing.assert_allclose(np.asarray(res.final), np.asarray(ref),
                               **REORDER_TOL)
    # emits arrive at the declared cumulative steps, final shape matches
    assert [t for t, _ in res.emits] == prog.emit_steps()
    assert res.emits[-1][1].shape == res.final.shape


def test_program_matches_oracle_reference():
    """Planner-free oracle: the whole program against the naive gather
    reference + eager updates (tolerance path — guards the arithmetic,
    not the rounding)."""
    from repro.kernels.ref import stencil_ref
    prog = _program(batch=1)
    res = compile_program(plan_program(prog, backends=["pallas"])).run(
        _state(prog))
    y = _state(prog)
    for i, seg in enumerate(prog.segments):
        for _ in range(seg.steps):
            y = stencil_ref(y, prog.problem.spec, boundary="periodic")
        if seg.update is not None:
            y = build_update(seg.update, prog.segment_problem(i))(y)
    np.testing.assert_allclose(np.asarray(res.final), np.asarray(y),
                               atol=1e-4)


def test_compiled_rollout_stream_and_segment_dedup():
    """stream() yields after every segment; segments with identical
    plans share ONE jitted sweep (no duplicate traces)."""
    segs = (Segment(2, UpdateOp("source", {"scale": 0.1})),
            Segment(2, UpdateOp("source", {"scale": 0.1})),
            Segment(2))
    prog = _program(segments=segs)
    compiled = compile_program(plan_program(prog, backends=["jnp"]))
    # all three segments share the same 2-step plan -> one jitted sweep
    assert len({id(f) for f in compiled.sweeps}) == 1
    # identical update op + shape -> one jitted update
    ups = [u for u in compiled.updates if u is not None]
    assert len({id(u) for u in ups}) == 1
    x = _state(prog)
    seen = list(compiled.stream(x))
    assert [t for _, t, _ in seen] == [2, 4, 6]
    np.testing.assert_array_equal(
        np.asarray(seen[-1][2]), np.asarray(compiled.run(x).final))


# ---------------------------------------------------------------------------
# Checkpointed, fault-tolerant driving (acceptance criteria)
# ---------------------------------------------------------------------------

def test_kill_and_resume_bit_exact(tmp_path):
    """A run killed mid-program resumes from its latest segment
    checkpoint and reproduces the uninterrupted result bit-exactly."""
    prog = _program()
    compiled = compile_program(plan_program(prog, backends=["pallas"]))
    x = _state(prog)
    uninterrupted = run_checkpointed(compiled, x)   # no checkpointing

    d = str(tmp_path / "ckpt")

    class Kill(RuntimeError):
        pass

    def die_in_segment_2(seg, attempt):
        if seg == 2:
            raise Kill("injected mid-program kill")

    with pytest.raises(Kill):
        run_checkpointed(compiled, x, directory=d,
                         fault_injector=die_in_segment_2)
    # segments 0 and 1 were checkpointed before the kill
    assert sorted(os.listdir(d)) == ["step_00000003", "step_00000005"]

    resumed = run_checkpointed(compiled, x, directory=d)
    np.testing.assert_array_equal(np.asarray(resumed.final),
                                  np.asarray(uninterrupted.final))
    assert [t for t, _ in resumed.emits] == [t for t, _ in
                                             uninterrupted.emits]
    for (_, a), (_, b) in zip(resumed.emits, uninterrupted.emits):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_guards_program_digest(tmp_path):
    prog = _program()
    compiled = compile_program(plan_program(prog, backends=["jnp"]))
    d = str(tmp_path / "ckpt")
    run_checkpointed(compiled, _state(prog), directory=d)
    other = _program(segments=(Segment(3), Segment(2), Segment(4)))
    other_c = compile_program(plan_program(other, backends=["jnp"]))
    with pytest.raises(ValueError, match="different rollout program"):
        run_checkpointed(other_c, _state(other), directory=d)


def test_keep_last_retention(tmp_path):
    """keep_last bounds the step_* population across a 4-boundary run."""
    prog = _program(segments=(Segment(1), Segment(1), Segment(1),
                              Segment(1, emit=True)))
    compiled = compile_program(plan_program(prog, backends=["jnp"],
                                            fuse=1))
    d = str(tmp_path / "ckpt")
    run_checkpointed(compiled, _state(prog), directory=d, keep_last=2)
    assert sorted(os.listdir(d)) == ["step_00000003", "step_00000004"]


def test_restart_policy_retries_transient_segment_failures():
    """A segment failing twice then succeeding is retried under the
    policy's backoff and completes bit-exactly; the budget resets per
    segment (on_success)."""
    prog = _program()
    compiled = compile_program(plan_program(prog, backends=["pallas"]))
    x = _state(prog)
    clean = run_checkpointed(compiled, x)
    fails = {"n": 0}

    def flaky(seg, attempt):
        if seg == 1 and attempt <= 2:
            fails["n"] += 1
            raise RuntimeError(f"transient failure {attempt}")

    policy = RestartPolicy(max_failures=3, backoff_s=0.001)
    out = run_checkpointed(compiled, x, restart=policy,
                           fault_injector=flaky)
    assert fails["n"] == 2
    assert policy.failures == 0            # reset after success
    np.testing.assert_array_equal(np.asarray(out.final),
                                  np.asarray(clean.final))
    # without a policy the failure propagates on first occurrence
    fails["n"] = 0
    with pytest.raises(RuntimeError, match="transient"):
        run_checkpointed(compiled, x, fault_injector=flaky)
    assert fails["n"] == 1


def test_restart_budget_exhaustion_propagates():
    prog = _program(segments=(Segment(2),))
    compiled = compile_program(plan_program(prog, backends=["jnp"]))

    def always_fail(seg, attempt):
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError, match="restart budget exhausted"):
        run_checkpointed(compiled, _state(prog),
                         restart=RestartPolicy(max_failures=2,
                                               backoff_s=0.001),
                         fault_injector=always_fail)


def test_hard_timeout_feeds_restart_path():
    """A HeartbeatMonitor hard timeout raises StepTimeout out of the
    segment; with a restart policy the segment re-runs (and times out
    again until the budget exhausts)."""
    prog = _program(segments=(Segment(2),), batch=1)
    compiled = compile_program(plan_program(prog, backends=["jnp"]))
    import time as _time
    slow = {"n": 0}

    def straggle(seg, attempt):
        slow["n"] += 1
        _time.sleep(0.03)

    mon = HeartbeatMonitor(hard_timeout_s=0.01)
    with pytest.raises(StepTimeout):
        run_checkpointed(compiled, _state(prog), monitor=mon,
                         fault_injector=straggle)
    # under a policy, StepTimeout is retried like any failure
    mon = HeartbeatMonitor(hard_timeout_s=0.01)
    with pytest.raises(RuntimeError, match="restart budget exhausted"):
        run_checkpointed(compiled, _state(prog), monitor=mon,
                         restart=RestartPolicy(max_failures=1,
                                               backoff_s=0.001),
                         fault_injector=straggle)
    assert slow["n"] == 3  # 1 (no policy) + initial + 1 retry


# ---------------------------------------------------------------------------
# Plan-cache program entries
# ---------------------------------------------------------------------------

def test_get_program_one_entry_hit_and_separation():
    cache = PlanCache()
    prog = _program()
    e1 = cache.get_program(prog, backends=["jnp"])
    assert cache.stats()["misses"] == 1 and len(cache) == 1
    e2 = cache.get_program(prog, backends=["jnp"])
    assert e2 is e1 and cache.stats()["hits"] == 1
    # the whole program is ONE entry; its fn returns (final, emits)
    x = _state(prog)
    final, emits = e1(x)
    assert len(emits) == 2
    ref = compile_program(plan_program(prog, backends=["jnp"])).run(x)
    np.testing.assert_array_equal(np.asarray(final), np.asarray(ref.final))
    # a plain sweep over the SAME problem at the same total steps is a
    # DIFFERENT entry (the program identity key slot)
    plain = dataclasses.replace(prog.problem, steps=prog.total_steps)
    e3 = cache.get(plain, backends=["jnp"])
    assert e3 is not e1 and len(cache) == 2
    # and a program differing only in an update param is a third
    changed = RolloutProgram(prog.problem, (
        Segment(3, UpdateOp("source", {"scale": 0.9, "seed": 1}),
                emit=True),) + prog.segments[1:])
    e4 = cache.get_program(changed, backends=["jnp"])
    assert e4 is not e1 and len(cache) == 3


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def test_server_rollout_streaming_and_batching():
    """Rollouts batch per (shape, next-segment signature), stream emits
    via rollout_results, settle finals like plain requests — and match
    the compiled program bit-exactly (same bucket batch)."""
    spec = SUITE["box2d_r1"]
    server = StencilServer(spec, steps=4, max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(3)
    states = [rng.standard_normal((24, 24)).astype(np.float32)
              for _ in range(4)]
    tickets = [server.submit_rollout(s, SEGMENTS) for s in states]
    out = server.flush()
    assert sorted(out) == tickets
    st = server.stats()
    # 4 rollouts x 3 segments ride 3 buckets (one per segment signature)
    assert st["batches"] == 3
    assert st["requests"] == 4
    assert st["latency"]["count"] == 4
    # bit-exact vs the compiled program at the same batch (bucket = 4)
    prob = StencilProblem(spec, (24, 24), boundary="periodic", steps=1,
                          batch=4)
    compiled = compile_program(
        RolloutProgram(prob, SEGMENTS), backends=["jnp"])
    ref = compiled.run(jnp.stack([jnp.asarray(s) for s in states]))
    for i, t in enumerate(tickets):
        np.testing.assert_array_equal(np.asarray(out[t]),
                                      np.asarray(ref.final[i]))
        ems = server.rollout_results(t)
        assert [s for s, _ in ems] == [3, 9]
        for (s, a), (rs, rb) in zip(ems, ref.emits):
            assert s == rs
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(rb[i]))
        assert server.rollout_done(t)
    # stream fully drained
    with pytest.raises(KeyError):
        server.rollout_results(tickets[0])


def test_server_rollout_incremental_drain_and_plain_coexistence():
    """step()-driven incremental drains; plain requests never share a
    rollout's bucket; repeat traffic hits the program cache entries."""
    spec = SUITE["box2d_r1"]
    server = StencilServer(spec, steps=2, max_batch=4, backends=["jnp"])
    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    t_roll = server.submit_rollout(
        x, [Segment(2, emit=True), Segment(2, emit=True)])
    t_plain = server.submit(x)
    server.step()               # admits both; async settles next turn
    server.step()               # settles segment 0 + the plain sweep
    ems = server.rollout_results(t_roll)
    assert [s for s, _ in ems] == [2]
    assert not server.rollout_done(t_roll)
    assert server.ready(t_plain)
    # plain 2-step result == first segment sweep (no update op)
    np.testing.assert_array_equal(np.asarray(server.results(t_plain)),
                                  np.asarray(ems[0][1]))
    server.flush()
    assert server.rollout_done(t_roll)
    assert [s for s, _ in server.rollout_results(t_roll)] == [4]
    misses0 = server.cache.stats()["misses"]
    t2 = server.submit_rollout(
        x, [Segment(2, emit=True), Segment(2, emit=True)])
    server.flush()
    assert server.cache.stats()["misses"] == misses0  # all cache hits
    assert server.rollout_done(t2)


def test_server_rollout_rejects_bad_input():
    server = StencilServer(SUITE["box2d_r1"], steps=2, backends=["jnp"])
    with pytest.raises(ValueError, match="rank"):
        server.submit_rollout(np.zeros((2, 8, 8), np.float32), [Segment(1)])
    with pytest.raises(ValueError, match="segment"):
        server.submit_rollout(np.zeros((8, 8), np.float32), [])
    vs = StencilServer(SUITE["box2d_r1"], steps=2, boundary="valid",
                       backends=["jnp"])
    with pytest.raises(ValueError, match="shape-preserving"):
        vs.submit_rollout(np.zeros((16, 16), np.float32), [Segment(1)])


# ---------------------------------------------------------------------------
# Bench gate
# ---------------------------------------------------------------------------

def test_bench_rollout_smoke():
    """The benchmark's tier-1 gate: modelled per-state traffic win for
    fused segment programs on >= 2 PAPER_SUITE cells."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks",
                                      "bench_rollout.py"), "--smoke"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SMOKE PASS" in out.stdout
