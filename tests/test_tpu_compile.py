"""Compile rehearsal: the Pallas kernels of the main path, compiled by
Mosaic for a described TPU v5e at real widths (no chip needed).

Interpret-mode tests cannot see what the chip's compiler refuses (a VMEM
window not aligned to the (8, 128) tile, a rank-3 contraction Mosaic
cannot lay out, a slab over the scoped-VMEM limit).  Each test lowers one
kernel family with ``interpret=False`` against a ``v5e:2x2`` topology and
asserts the executable holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers all import
this file.  The persistent compilation cache is off around these compiles
(a described chip's entries cannot be read back).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import stencil_spec as ss
from repro.kernels import ops
from repro.kernels.banded_mixer import banded_mixer_pallas_call

SUITE = ss.PAPER_SUITE()
GRID_2D = (4096, 4096)
GRID_3D = (256, 256, 256)
BLOCK_2D = (128, 128)
BLOCK_3D = (8, 8, 128)       # configs/paper_stencil.py's 3-D tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, sharding, dtype=jnp.float32):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name,grid,block", [
    ("star2d_r2", GRID_2D, BLOCK_2D),
    ("box2d_r1", GRID_2D, BLOCK_2D),
    ("box3d_r1", GRID_3D, BLOCK_3D),
])
def test_single_step_kernel_compiles(one_chip, name, grid, block):
    spec = SUITE[name]
    compiled = _compile(lambda x: ops.stencil_matrixized(
        x, spec=spec, block=block, boundary="periodic", interpret=False),
        grid, one_chip)
    # the state and its output are the only large buffers on the device
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * int(np.prod(grid))


@pytest.mark.parametrize("scratch", ["pingpong", "single"])
def test_inkernel_sweep_compiles(one_chip, scratch):
    spec = SUITE["star2d_r2"]
    _compile(lambda x: ops.stencil_sweep_matrixized(
        x, spec=spec, steps=4, block=BLOCK_2D, boundary="periodic",
        interpret=False, scratch=scratch), GRID_2D, one_chip)


def test_inkernel_sweep_3d_compiles(one_chip):
    spec = SUITE["star3d_r1"]
    _compile(lambda x: ops.stencil_sweep_matrixized(
        x, spec=spec, steps=2, block=BLOCK_3D, boundary="periodic",
        interpret=False), (64, 64, 128), one_chip)


@pytest.mark.parametrize("steps", [1, 4])
def test_batched_kernel_compiles(one_chip, steps):
    spec = SUITE["box2d_r1"]
    if steps == 1:
        fn = lambda x: ops.stencil_matrixized(  # noqa: E731
            x, spec=spec, block=BLOCK_2D, boundary="periodic",
            interpret=False)
    else:
        fn = lambda x: ops.stencil_sweep_matrixized(  # noqa: E731
            x, spec=spec, steps=steps, block=BLOCK_2D, boundary="periodic",
            interpret=False)
    _compile(fn, (8,) + GRID_2D, one_chip)


def test_scenario_aux_operands_compile(one_chip):
    grid = (1024, 1024)
    spec = SUITE["star2d_r2"].with_field(
        ss.random_coeff_field(grid, seed=1),
        domain_mask=ss.random_domain_mask(grid, seed=2))
    _compile(lambda x: ops.stencil_sweep_matrixized(
        x, spec=spec, steps=3, block=BLOCK_2D, boundary="periodic",
        interpret=False), grid, one_chip)


def test_mesh_cell_stepper_compiles_and_fits(topo, monkeypatch):
    """The ``star2d_r2_49k`` deployment at full size: 49152² on the (2,2)
    mesh through ``api.plan`` -> ``api.compile``.  The chunk's kernel and
    the interior recompute's carry their own names, and a chip holds the
    arguments, the output, the temporaries and one more queued output
    (two calls in flight) in its 16 GiB."""
    import json
    import pathlib
    from jax.sharding import NamedSharding
    from repro import api
    from repro.kernels import stencil_mxu
    from repro.launch.mesh import make_mesh
    cfg = json.loads((pathlib.Path(__file__).parents[1] / "chipbench"
                      / "configs" / "star2d_r2_49k.json").read_text())
    m = cfg["mesh"]
    mesh = make_mesh(m["shape"], m["axes"], devices=topo.devices[:4])
    problem = api.StencilProblem(SUITE[cfg["suite"]], tuple(cfg["grid"]),
                                 dtype=cfg["dtype"], boundary=cfg["boundary"],
                                 steps=16, mesh=mesh,
                                 grid_axes=tuple(m["grid_axes"]))
    monkeypatch.setattr(stencil_mxu, "resolve_interpret",
                        lambda interpret=None: False)
    run = api.compile(api.plan(problem), mesh=mesh)
    x = jax.ShapeDtypeStruct(tuple(cfg["grid"]), jnp.float32,
                             sharding=NamedSharding(mesh, run.stepper.pspec))
    compiled = run.stepper.fn.lower(x).compile()
    text = compiled.as_text()
    assert "%stencil_sweep." in text and "%stencil_sweep_interior." in text
    mem = compiled.memory_analysis()
    shard = 4 * int(np.prod(cfg["grid"])) // mesh.devices.size  # f32
    assert mem.output_size_in_bytes == shard
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + shard)
    assert held <= 16 * 2**30, held / 2**30


@pytest.mark.parametrize("depthwise", [False, True])
def test_banded_mixer_compiles(one_chip, depthwise):
    """The sequence mixer shares the stencil kernels' window layout: a
    (128 + W - 1)-row window is not tile-aligned either."""
    band = np.linspace(0.1, 0.4, 4 * (256 if depthwise else 1),
                       dtype=np.float32).reshape((4, 256) if depthwise
                                                 else (4,))
    _compile(lambda x: banded_mixer_pallas_call(x, band, interpret=False),
             (2048, 256), one_chip)


def test_interpret_resolves_by_platform():
    """One decision: the interpreter on the CPU backend, Mosaic on a TPU;
    an explicit False always compiles."""
    from repro.kernels.ops import resolve_interpret
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_hardware_is_keyed_by_device_kind():
    """The roofline table is looked up by ``device_kind``; a chip missing
    from it is an error, not a default."""
    import types
    from repro.launch.mesh import TPU_V5E, hardware_for_device
    assert hardware_for_device(
        types.SimpleNamespace(device_kind="TPU v5 lite")) is TPU_V5E
    with pytest.raises(KeyError, match="TPU v9"):
        hardware_for_device(types.SimpleNamespace(device_kind="TPU v9"))
