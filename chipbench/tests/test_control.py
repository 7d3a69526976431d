"""The control, the reference at ``high`` put in the program's place,
fails each cell's limit where the program passes it (at CPU sizes; the
chip readings the limits were set from are in ``limits/`` and PERF.md).
The cases are every one-chip cell of BENCHMARK.json and a 3-D cell that
has no limit file yet, which ``control.measure`` reads all the same."""
import functools

import jax
import pytest

from chipbench import control
from chipbench.tests.small import (BOX3D, BOX3D_LIMITS, box3d_cell, listed,
                                   small_cell)

ONE_CHIP = listed(1)
#: each case makes its cut cell, and gives the limit it is held to where
#: the cell has no limit file: the 3-D cell of the tests' own, until
#: BENCHMARK.json lists one of that name
CASES = [pytest.param(functools.partial(small_cell, n), None, id=n)
         for n in ONE_CHIP]
if BOX3D["name"] not in ONE_CHIP:
    CASES.append(pytest.param(box3d_cell, BOX3D_LIMITS, id=BOX3D["name"]))


@pytest.mark.parametrize("make, limits", CASES)
def test_control_fails_where_the_program_passes(make, limits):
    cell = make()
    if limits is None:
        limits = cell.limits
    else:
        assert cell.limits is None      # read before it has a limit file
    rows, summary = control.measure(cell, (1, 2**33 + 3, 77), 1.0,
                                    jax.devices()[:1])
    assert set(summary) == set(rows[0]["program"]) == set(
        limits["compared"])
    for name, lim in limits["compared"].items():
        assert summary[name]["seeds"] == 3
        assert summary[name]["program_max"] <= lim["limit"]
        assert summary[name]["control_min"] > lim["limit"]
