"""``control_in_place.py`` reads what ``control.py`` reads, to the bit: the
control evolved in place from the last call's input, compared with the
reference on the host, gives the same ``rel_err`` as the control evolved
beside that input and compared on the device.  The cases are the listed
sweep cells, cut: one chip in this process, four in a child with four
host devices."""
import subprocess
import sys
import textwrap

import jax
import pytest

from chipbench import control_in_place, run
from chipbench.tests.small import listed, small_cell


def same_readings(name: str, devices) -> dict:
    cell = small_cell(name)
    path = cell.path.Path(cell.config, cell.traffic, 2**33 + 5, devices)
    path.setup()
    path.window(0.5)
    path.release()
    program = path.check()
    beside = path.check(control=True)
    in_place = control_in_place.control_reading(path)
    assert in_place == beside, (in_place, beside)
    assert path.last_in is None
    assert program["rel_err"] < beside["rel_err"]
    return beside


@pytest.mark.parametrize("name", listed(1, "sweep"))
def test_one_chip_reads_as_control_py(name):
    same_readings(name, jax.devices()[:1])


@pytest.mark.parametrize("name", listed(4, "sweep"))
def test_mesh_reads_as_control_py(name):
    code = textwrap.dedent("""
        import sys; sys.path[:0] = [%r, %r]
        import jax
        from chipbench.tests.test_control_in_place import same_readings
        print("same", same_readings(%r, jax.devices()[:4]))
    """ % (str(run.ROOT / "src"), str(run.ROOT), name))
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "same {'rel_err'" in p.stdout, p.stderr[-3000:]
