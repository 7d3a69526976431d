"""Multi-device behaviour, exercised in subprocesses with 8 fake CPU
devices (the main pytest process stays at 1 device by design — see the
dry-run contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(body: str, n: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = _SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_distributed_stencil_matches_single_device():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import stencil_spec as ss
        from repro.core.distributed import make_distributed_stepper
        from repro.core.engine import StencilEngine
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("gx", "gy"))
        spec = ss.box(2, 1, seed=5)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(64, 32)), jnp.float32)
        for periodic in (True, False):
            for overlap in (True, False):
                step = make_distributed_stepper(spec, mesh, ("gx", "gy"),
                                                periodic=periodic, overlap=overlap)
                eng = StencilEngine(spec, boundary="periodic" if periodic else "zero")
                err = float(jnp.abs(step(x) - eng(x)).max())
                assert err < 1e-5, (periodic, overlap, err)
        step5 = make_distributed_stepper(spec, mesh, ("gx", "gy"), steps=5)
        eng = StencilEngine(spec, boundary="periodic")
        ref = x
        for _ in range(5): ref = eng(ref)
        assert float(jnp.abs(step5(x) - ref).max()) < 1e-5
    """)


def test_halo_exchange_hlo_contains_collective_permute():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import stencil_spec as ss
        from repro.core.distributed import make_distributed_stepper
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("gx", "gy"))
        spec = ss.star(2, 2, seed=1)
        step = make_distributed_stepper(spec, mesh, ("gx", "gy"))
        x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
        txt = jax.jit(step).lower(x).compile().as_text()
        print("PERMUTES", txt.count("collective-permute"))
    """)
    assert int(out.split("PERMUTES")[1].split()[0]) > 0


def test_sharded_train_step_and_elastic_restore():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch.cells import _state_shardings
        from repro.optim.adamw import adamw
        from repro.sharding import rules
        from repro.train.train_step import init_train_state, make_train_step
        from repro.launch.input_specs import train_batch_specs, sample_from_specs
        from repro.checkpoint.checkpointer import save_checkpoint, restore_checkpoint
        import tempfile, os

        cfg = get_smoke_config("tinyllama_1_1b")
        opt = adamw(lr=1e-3)
        batch = sample_from_specs(train_batch_specs(cfg, 4, 16), cfg, seed=1)
        step_fn = make_train_step(cfg, opt, ce_chunk=8)

        mesh_a = make_mesh((4, 2), ("data", "model"))
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        sh_a = _state_shardings(mesh_a, jax.eval_shape(lambda: state))
        state_a = jax.device_put(state, sh_a)
        with rules.activate(mesh_a):
            st_a, m_a = jax.jit(step_fn, in_shardings=(sh_a, rules.batch_shardings(mesh_a, jax.eval_shape(lambda: batch))),
                                out_shardings=(sh_a, None))(state_a, batch)
        # single-device reference
        st_ref, m_ref = jax.jit(step_fn)(state, batch)
        assert abs(float(m_a["loss"]) - float(m_ref["loss"])) < 1e-4, (float(m_a["loss"]), float(m_ref["loss"]))

        # checkpoint from mesh A, restore onto mesh B with different shape
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, st_a)
        mesh_b = make_mesh((2, 2, 2), ("pod", "data", "model"))
        sh_b = _state_shardings(mesh_b, jax.eval_shape(lambda: state))
        st_b, _ = restore_checkpoint(d, 1, st_ref, shardings=sh_b)
        with rules.activate(mesh_b):
            st_b2, m_b = jax.jit(step_fn, out_shardings=(sh_b, None))(st_b, batch)
        st_ref2, m_ref2 = jax.jit(step_fn)(st_ref, batch)
        assert abs(float(m_b["loss"]) - float(m_ref2["loss"])) < 1e-4
        print("ELASTIC OK")
    """)


def test_shard_map_dp_gradient_sync_with_compression():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        w = jnp.asarray(np.random.default_rng(0).normal(size=(16, 4)), jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(32, 16)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(2).normal(size=(32, 4)), jnp.float32)

        def dp(w, x, y):
            g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
            gw = g.astype(jnp.bfloat16)  # compress before the wire
            # NOTE: check=False — with VMA/rep checking on, out_specs=P()
            # stacks an implicit psum on top of pmean (measured exactly 8x)
            return jax.lax.pmean(gw.astype(jnp.float32), axis_name="data")

        f = jax.shard_map(dp, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                          out_specs=P(), check_vma=False)
        g_dp = f(w, x, y)
        g_ref = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        err = float(jnp.abs(g_dp - g_ref).max()) / (float(jnp.abs(g_ref).max()) + 1e-9)
        assert err < 0.02, err
        print("DP-COMPRESS OK")
    """)


def test_sharding_rules_divisibility():
    run_with_devices("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.sharding.rules import maybe_spec, resolve_axis
        mesh = make_mesh((4, 2), ("data", "model"))
        # divisible: sharded; non-divisible: dropped
        assert resolve_axis("tp", mesh, 8) == "model"
        assert resolve_axis("tp", mesh, 7) is None
        assert resolve_axis("dp", mesh, 8) == "data"
        assert resolve_axis("dp", mesh, 2) is None
        s = maybe_spec(mesh, (16, 6), ("fsdp", "tp"))
        assert s == P("data", "model")
        s2 = maybe_spec(mesh, (3, 6), ("fsdp", "tp"))
        assert s2 == P(None, "model")
        print("RULES OK")
    """)


def test_fused_distributed_sweep_parity_two_device_mesh():
    """Acceptance: compile(plan) on a 2-device mesh with fuse=T>1 matches
    the sequential single-device sweep (periodic + zero), and the emitted
    stepper performs exactly ONE T*r-deep halo exchange per fused chunk
    (counted as ppermutes in the jaxpr)."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.core.engine import StencilEngine
        from repro.launch.mesh import make_mesh
        from repro.kernels.ref import stencil_ref

        mesh = make_mesh((2,), ("gx",))
        spec = api.box(2, 1, seed=5)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(32, 24)),
                        jnp.float32)
        for boundary in ("periodic", "zero"):
            prob = api.StencilProblem(spec, (32, 24), boundary=boundary,
                                      steps=7, mesh=mesh,
                                      grid_axes=("gx", ""))
            p = api.plan(prob, fuse=3, backends=["jnp"])
            assert p.fuse_schedule == (3, 3, 1), p.fuse_schedule
            assert p.halo_strategy == "exchange" and p.halo_width == 3
            run = api.compile(p, mesh=mesh)
            ref = x
            for _ in range(7):
                ref = stencil_ref(ref, spec, boundary=boundary)
            err = float(jnp.abs(run(x) - ref).max())
            assert err < 1e-5, (boundary, err)
            # parity with the single-device fused sweep too
            eng = StencilEngine(spec, boundary=boundary)
            err_sweep = float(jnp.abs(run(x) - eng.sweep(x, 7, fuse=3)).max())
            assert err_sweep < 1e-5, (boundary, err_sweep)
            # ONE deep exchange per fused chunk: 3 chunks x 1 sharded axis
            # x 2 directions = 6 ppermutes, regardless of T
            n_pp = str(jax.make_jaxpr(run.global_fn)(x)).count("ppermute")
            assert n_pp == 6, (boundary, n_pp)

        # no backend pin: the planner's default (pallas) must also compile
        # and run under the always-jitted distributed stepper
        prob = api.StencilProblem(spec, (32, 24), boundary="periodic",
                                  steps=2, mesh=mesh, grid_axes=("gx", ""))
        p = api.plan(prob, fuse=2)
        assert p.backend == "pallas", p.backend
        run = api.compile(p, mesh=mesh)
        ref = x
        for _ in range(2):
            ref = stencil_ref(ref, spec, boundary="periodic")
        err = float(jnp.abs(run(x) - ref).max())
        assert err < 1e-5, err
        print("FUSED DISTRIBUTED OK")
    """)


def test_fused_distributed_sweep_2d_mesh_and_3d():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.launch.mesh import make_mesh
        from repro.kernels.ref import stencil_ref

        # 2-D grid over a (2,2) mesh, star r=2, both boundaries
        mesh = make_mesh((2, 2), ("gx", "gy"))
        spec = api.star(2, 2, seed=1)
        x = jnp.asarray(np.random.default_rng(4).normal(size=(32, 32)),
                        jnp.float32)
        for boundary in ("periodic", "zero"):
            prob = api.StencilProblem(spec, (32, 32), boundary=boundary,
                                      steps=4, mesh=mesh,
                                      grid_axes=("gx", "gy"))
            p = api.plan(prob, fuse=2, backends=["jnp"])
            run = api.compile(p, mesh=mesh)
            ref = x
            for _ in range(4):
                ref = stencil_ref(ref, spec, boundary=boundary)
            err = float(jnp.abs(run(x) - ref).max())
            assert err < 1e-5, (boundary, err)
            n_pp = str(jax.make_jaxpr(run.global_fn)(x)).count("ppermute")
            assert n_pp == 2 * 2 * 2, n_pp  # 2 chunks x 2 axes x 2 dirs

        # 3-D star over a (2,2,2) mesh
        mesh3 = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
        spec3 = api.star(3, 1, seed=2)
        x3 = jnp.asarray(np.random.default_rng(7).normal(size=(16, 16, 16)),
                         jnp.float32)
        for boundary in ("periodic", "zero"):
            prob = api.StencilProblem(spec3, (16, 16, 16), boundary=boundary,
                                      steps=4, mesh=mesh3,
                                      grid_axes=("gx", "gy", "gz"))
            run = api.compile(api.plan(prob, fuse=2, backends=["jnp"]),
                              mesh=mesh3)
            ref = x3
            for _ in range(4):
                ref = stencil_ref(ref, spec3, boundary=boundary)
            err = float(jnp.abs(run(x3) - ref).max())
            assert err < 1e-4, (boundary, err)
        print("FUSED 2D/3D MESH OK")
    """)


def test_fused_distributed_inkernel_one_exchange_per_chunk():
    """In-kernel temporal blocking under the fused distributed stepper:
    the strategy swaps only the chunk core, so a T-deep chunk still costs
    exactly ONE T*r-deep halo exchange (same ppermute count as operator
    fusion), and the result stays bit-exact against the single-device
    in-kernel sweep and exact-to-tolerance against the sequential
    reference (periodic + zero)."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.core.engine import StencilEngine
        from repro.launch.mesh import make_mesh
        from repro.kernels.ref import stencil_ref

        mesh = make_mesh((2,), ("gx",))
        spec = api.star(2, 2, seed=1)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(32, 24)),
                        jnp.float32)
        for boundary in ("periodic", "zero"):
            prob = api.StencilProblem(spec, (32, 24), boundary=boundary,
                                      steps=7, mesh=mesh,
                                      grid_axes=("gx", ""))
            p = api.plan(prob, fuse=3, fuse_strategy="inkernel")
            assert p.fuse_strategy == "inkernel" and p.backend == "pallas"
            assert p.fuse_schedule == (3, 3, 1), p.fuse_schedule
            assert p.halo_strategy == "exchange" and p.halo_width == 6
            run = api.compile(p, mesh=mesh)
            ref = x
            for _ in range(7):
                ref = stencil_ref(ref, spec, boundary=boundary)
            err = float(jnp.abs(run(x) - ref).max())
            assert err < 1e-4, (boundary, err)
            # single-device in-kernel sweep parity
            eng = StencilEngine(spec, backend="pallas", block=p.block,
                                boundary=boundary)
            sweep = eng.sweep(x, 7, fuse=3, strategy="inkernel")
            err_sweep = float(jnp.abs(run(x) - sweep).max())
            assert err_sweep < 1e-5, (boundary, err_sweep)
            # ONE deep exchange per fused chunk, same as operator fusion:
            # 3 chunks x 1 sharded axis x 2 directions = 6 ppermutes
            n_pp = str(jax.make_jaxpr(run.global_fn)(x)).count("ppermute")
            assert n_pp == 6, (boundary, n_pp)
        print("FUSED DISTRIBUTED INKERNEL OK")
    """)


def test_distributed_stepper_unsharded_axis_regression():
    """One sharded + one unsharded spatial axis: the overlap splice used to
    shape-error (the interior shrank the unsharded axis but the splice index
    kept slice(None)); the unsharded axis now gets its boundary locally."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import stencil_spec as ss
        from repro.core.distributed import make_distributed_stepper
        from repro.core.engine import StencilEngine
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("gx",))
        spec = ss.box(2, 1, seed=5)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(32, 24)),
                        jnp.float32)
        for periodic in (True, False):
            for overlap in (True, False):
                step = make_distributed_stepper(spec, mesh, ("gx", ""),
                                                periodic=periodic,
                                                overlap=overlap)
                eng = StencilEngine(
                    spec, boundary="periodic" if periodic else "zero")
                err = float(jnp.abs(step(x) - eng(x)).max())
                assert err < 1e-5, (periodic, overlap, err)
        print("UNSHARDED AXIS OK")
    """)


def test_fused_distributed_batched_states_one_exchange_per_chunk():
    """Batch support in the fused distributed stepper: B independent
    states ride one compiled call as a leading replicated axis.  The
    spatial protocol is untouched, so the ppermute count is PROVABLY
    unchanged vs the unbatched stepper (same jaxpr census), and the
    batched result matches the single-state stepper per state (to the
    usual multidevice tolerance — XLA:CPU fuses the rank-3 local blocks
    differently than rank-2 ones; both strategies, periodic + zero)."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import api
        from repro.launch.mesh import make_mesh
        from repro.kernels.ref import stencil_ref

        mesh = make_mesh((2,), ("gx",))
        spec = api.star(2, 2, seed=1)
        B = 3
        xb = jnp.asarray(np.random.default_rng(3).normal(size=(B, 32, 24)),
                         jnp.float32)
        for boundary in ("periodic", "zero"):
            for strategy in ("operator", "inkernel"):
                kw = dict(boundary=boundary, steps=7, mesh=mesh,
                          grid_axes=("gx", ""))
                prob_b = api.StencilProblem(spec, (32, 24), batch=B, **kw)
                prob_1 = api.StencilProblem(spec, (32, 24), **kw)
                pins = dict(fuse=3, fuse_strategy=strategy)
                run_b = api.compile(api.plan(prob_b, **pins), mesh=mesh)
                run_1 = api.compile(api.plan(prob_1, **pins), mesh=mesh)
                try:
                    run_b(xb[0])
                    raise SystemExit("unbatched input not rejected")
                except ValueError as e:
                    assert "batch" in str(e)
                try:
                    run_1(xb)   # stray lead axis on an unbatched plan
                    raise SystemExit("stray lead axis not rejected")
                except ValueError as e:
                    assert "batch" in str(e)
                out = run_b(xb)
                # per-state parity vs the single-state distributed stepper
                for i in range(B):
                    err = float(jnp.abs(out[i] - run_1(xb[i])).max())
                    assert err < 1e-5, (boundary, strategy, i, err)
                # oracle
                ref = xb
                for _ in range(7):
                    ref = stencil_ref(ref, spec, boundary=boundary)
                err = float(jnp.abs(out - ref).max())
                assert err < 1e-4, (boundary, strategy, err)
                # ppermute census: 3 chunks x 1 sharded axis x 2 dirs,
                # independent of the batch axis
                n_b = str(jax.make_jaxpr(run_b.global_fn)(xb)).count(
                    "ppermute")
                n_1 = str(jax.make_jaxpr(run_1.global_fn)(xb[0])).count(
                    "ppermute")
                assert n_b == n_1 == 6, (boundary, strategy, n_b, n_1)
        print("BATCHED DISTRIBUTED OK")
    """, timeout=600)


def test_distributed_3d_stencil():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import stencil_spec as ss
        from repro.core.distributed import make_distributed_stepper
        from repro.core.engine import StencilEngine
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
        spec = ss.star(3, 1, seed=2)
        x = jnp.asarray(np.random.default_rng(7).normal(size=(16, 24, 32)),
                        jnp.float32)
        step = make_distributed_stepper(spec, mesh, ("gx", "gy", "gz"),
                                        periodic=True)
        eng = StencilEngine(spec, boundary="periodic")
        err = float(jnp.abs(step(x) - eng(x)).max())
        assert err < 1e-5, err
        print("3D DISTRIBUTED OK", err)
    """)
