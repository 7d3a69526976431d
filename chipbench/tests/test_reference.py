"""The plain reference against a loop over the taps in numpy (float64),
on one state, a batch, and a state sharded over a mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import reference, run

COEFFS = np.asarray(run.Cell("star2d_r2.sweep").config["stencil"]
                    ["gather_coeffs"])


def numpy_step(x):
    out = np.zeros_like(x, np.float64)
    for off, c in reference.taps(COEFFS):
        out += c * np.roll(x, tuple(-o for o in off), (-2, -1))
    return out


def test_taps_of_the_star():
    tp = reference.taps(COEFFS)
    assert len(tp) == 9
    assert {o for o, _ in tp} == {(0, 0), (-2, 0), (-1, 0), (1, 0), (2, 0),
                                  (0, -2), (0, -1), (0, 1), (0, 2)}


@pytest.mark.parametrize("shape", [(32, 40), (3, 16, 24)])
def test_evolve_matches_numpy(shape):
    x = np.random.default_rng(0).standard_normal(shape)
    want = x
    for _ in range(5):
        want = numpy_step(want)
    got = reference.evolve(jnp.asarray(x, jnp.float32), COEFFS, 5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-6)


def test_high_precision_is_coarser():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((64, 64)),
                    jnp.float32)
    ref = reference.evolve(x, COEFFS, 8)
    high = reference.evolve(x, COEFFS, 8, "high")
    err = reference.rel_err(high, ref)
    assert 1e-6 < err < 1e-3


def test_high_rounds_to_bf16_in_the_compiled_program():
    # the control's bf16 parts come from an op the compiler keeps, not from
    # an astype round trip it may drop as a pair of converts
    from repro.core.stencil_spec import PAPER_SUITE
    c = PAPER_SUITE()["box3d_r1"].gather_coeffs
    key = (c.shape, tuple(c.ravel().tolist()))
    x = jax.ShapeDtypeStruct((8, 16, 128), jnp.float32)
    hlo = reference._evolve_fn(key, 2, "high", None).lower(x).compile()
    assert "reduce-precision(" in hlo.as_text()
    v = jnp.asarray(np.random.default_rng(3).standard_normal(256),
                    jnp.float32)
    hi, lo = reference._split_bf16(v)
    for part in (hi, lo):
        np.testing.assert_array_equal(
            part, part.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(jnp.max(jnp.abs(v - hi - lo) / jnp.abs(v))) < 2**-16


def test_sharded_evolve_matches_one_device():
    # runs in a child with four host devices; the parent's JAX has one
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import sys; sys.path[:0] = [%r, %r]
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from chipbench import reference, run
        c = np.asarray(run.Cell("star2d_r2.sweep").config["stencil"]["gather_coeffs"])
        mesh = jax.make_mesh((2, 2), ("a", "b"))
        sh = NamedSharding(mesh, P("a", "b"))
        x = jnp.asarray(np.random.default_rng(2).standard_normal((64, 96)), jnp.float32)
        for prec in ("highest", "high"):
            one = reference.evolve(x, c, 6, prec)
            many = reference.evolve(jax.device_put(x, sh), c, 6, prec,
                                    sharding=sh)
            assert many.sharding == sh
            assert float(jnp.max(jnp.abs(one - many))) < 1e-6
        print("ok")
    """ % (str(run.ROOT / "src"), str(run.ROOT)))
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and "ok" in p.stdout, p.stderr[-2000:]
