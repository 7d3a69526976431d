"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, runs the rest of a run at
a CPU size, with one fault the cell can have planted in what the window
drives, and sees ``correct`` false; the same run with nothing planted
reads ``correct``.  The sweep tests run every one-chip sweep cell of
BENCHMARK.json and a 3-D cell not listed yet (``small.box3d_cell``), the
served tests every one-chip served cell.  The last test does the same for
the mesh cells, whose fault is the exchange between chips left out, in a
child with four host devices.
"""
import functools
import subprocess
import sys
import textwrap
import time

import jax
import pytest

from chipbench import run
from chipbench.tests.small import (BOX3D, BOX3D_LIMITS, MESH, MESH_LIMITS,
                                   box3d_cell, listed, small_cell)
from repro.core import plan_cache

SEED = 2**33 + 11

#: each case makes its cut cell; the 3-D cell of the tests' own until
#: BENCHMARK.json lists one of that name
SWEEP = [pytest.param(functools.partial(small_cell, n), id=n)
         for n in listed(1, "sweep")]
if BOX3D["name"] not in listed(1, "sweep"):
    SWEEP.append(pytest.param(functools.partial(box3d_cell, BOX3D_LIMITS),
                              id=BOX3D["name"]))
SERVE = [pytest.param(functools.partial(small_cell, n), id=n)
         for n in listed(1, "serve")]


def run_small(make, plant=None, **traffic) -> dict:
    cell = make()
    cell.traffic = dict(cell.traffic, **traffic)
    if plant is not None:
        base = cell.path.Path

        class Broken(base):
            def setup(self):
                super().setup()
                plant(self)
        cell.path = type("paths", (), {"Path": Broken})
    return run.run(cell, SEED, 1.0, False, jax.devices()[:cell.chips],
                   time.perf_counter())


@pytest.mark.parametrize("make", SWEEP)
def test_sound_sweep_is_correct(make):
    assert run_small(make)["correct"]


@pytest.mark.parametrize("make", SWEEP)
def test_sweep_state_left_unchanged(make):
    def plant(p):
        f = p.fn
        # the call runs, and its state comes back as it went in
        p.fn = lambda x: (f(x).block_until_ready(), x)[1]
    assert not run_small(make, plant)["correct"]


@pytest.mark.parametrize("make", SWEEP)
def test_sweep_answer_altered(make):
    def plant(p):
        f = p.fn
        p.fn = lambda x: f(x).at[0, 0].add(1.0)
    assert not run_small(make, plant)["correct"]


@pytest.mark.parametrize("loop", [{}, {"rate_per_s": 20}],
                         ids=["closed", "open"])
@pytest.mark.parametrize("make", SERVE)
def test_sound_serve_is_correct(make, loop):
    assert run_small(make, **loop)["correct"]


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
@pytest.mark.parametrize("make", SERVE)
def test_serve_fault(monkeypatch, make, fault):
    orig = plan_cache.CachedExecutable.dispatch

    def dispatch(self, x):
        out = orig(self, x)
        if fault == "answer_altered":
            return out.at[..., 0, 0].add(1.0)
        # rows of the bucket's second half (a lone state, too) not advanced
        if x.ndim == 2:
            return x
        return out.at[x.shape[0] // 2:].set(x[x.shape[0] // 2:])
    monkeypatch.setattr(plan_cache.CachedExecutable, "dispatch", dispatch)
    assert not run_small(make)["correct"]


#: each mesh case, as the child makes its cut cell: the listed four-chip
#: sweep cells, or the mesh cell that is not listed yet
MESHES = ([pytest.param(f"small_cell({n!r})", id=n)
           for n in listed(4, "sweep")]
          or [pytest.param(f"small_cell({MESH!r}, limits={MESH_LIMITS!r})",
                           id=MESH["name"])])


@pytest.mark.parametrize("make", MESHES)
def test_mesh_exchange_left_out(make):
    code = textwrap.dedent("""
        import sys, time; sys.path[:0] = [%r, %r]
        import jax
        from chipbench import run
        from chipbench.tests.small import small_cell
        from repro.core import distributed

        def no_exchange(block, axis, r, mesh_axis, periodic):
            lo = jax.lax.slice_in_dim(block, 0, r, axis=axis)
            hi = jax.lax.slice_in_dim(block, block.shape[axis] - r,
                                      block.shape[axis], axis=axis)
            return hi, lo        # the shard's own edges, no neighbour's

        def one(broken):
            cell = %s
            if broken:
                distributed._exchange_axis = no_exchange
            res = run.run(cell, 5, 1.0, False, jax.devices()[:4],
                          time.perf_counter())
            return res["correct"]
        print("sound", one(False), "broken", one(True))
    """ % (str(run.ROOT / "src"), str(run.ROOT), make))
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "sound True broken False" in p.stdout, p.stderr[-3000:]
