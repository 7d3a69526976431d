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
        one = reference.evolve(x, c, 6)
        many = reference.evolve(jax.device_put(x, sh), c, 6, sharding=sh)
        assert many.sharding == sh
        assert float(jnp.max(jnp.abs(one - many))) < 1e-6
        print("ok")
    """ % (str(run.ROOT / "src"), str(run.ROOT)))
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and "ok" in p.stdout, p.stderr[-2000:]
