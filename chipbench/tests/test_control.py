"""The control, the reference at ``high`` put in the program's place,
fails each cell's limit where the program passes it (at CPU sizes; the
chip readings the limits were set from are in ``limits/`` and PERF.md)."""
import jax
import pytest

from chipbench import control
from chipbench.tests.small import small_cell


@pytest.mark.parametrize("name", ["star2d_r2.sweep",
                                  "star2d_r2.ensemble"])
def test_control_fails_where_the_program_passes(name):
    cell = small_cell(name)
    limit = cell.limits["compared"]["rel_err"]["limit"]
    for seed in (1, 2**33 + 3, 77):
        r = control.readings(cell, seed, 1.0, jax.devices()[:1])
        assert r["program"]["rel_err"] <= limit
        assert r["control"]["rel_err"] > limit
