import pytest

from chipbench import run, work


def test_star2d_r2_work_per_call():
    cell = run.Cell("star2d_r2.sweep")
    points = 16384 * 16384
    flops, nbytes = work.call_work(cell.config, points, 16)
    assert work.taps(cell.config) == 9
    assert flops == 2 * 9 * points * 16
    assert nbytes == 2 * 4 * points


def test_star2d_r2_49k_work_per_shard():
    config = run.load_json("configs", "star2d_r2_49k")
    assert config["grid"] == [49152, 49152]
    shard = 49152 * 49152 // 4
    flops, nbytes = work.call_work(config, shard, 16)
    assert flops == 2 * 9 * 24576 * 24576 * 16
    assert nbytes == 2 * 4 * 24576 * 24576


def test_bound_names_the_binding_peak():
    peaks = work.peaks_for("TPU v5 lite")
    flops, nbytes = work.call_work(run.Cell("star2d_r2.sweep").config,
                                   16384 * 16384, 16)
    t, binds = work.bound_s(flops, nbytes, peaks)
    assert binds == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    t, binds = work.bound_s(1e15, 1.0, peaks)
    assert binds == "compute" and t == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v4")
