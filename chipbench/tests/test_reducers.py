"""Each per-layer reader on small traces recorded on a TPU v5e.

``data/<workload>.xplane.pb`` is the profiler's trace of a short window
of the cell's path at a cut size (sweep: 2048², 16 calls; served: 30
requests at 300/s), and ``data/<workload>.json``
the window's facts.  The numbers pinned here were read from those traces
when they were recorded.  ``data/spans/`` holds the recordings of the
program with its spans and kernel names (``record.py``): every cell that
has one there is found by its file name, and every metric of the cell
reads on it, each as its ``read`` pins it.
"""
import json
import pathlib

import pytest

from chipbench import run, trace, work

DATA = pathlib.Path(__file__).resolve().parent / "data"
SPANS_DATA = DATA / "spans"
PEAKS = work.peaks_for("TPU v5 lite")
#: the cells that have a recording with spans
RECORDED = sorted(p.name[:-len(".xplane.pb")]
                  for p in SPANS_DATA.glob("*.xplane.pb"))


def readings(name: str):
    cell = run.Cell(name)
    fx = json.loads((DATA / f"{name}.json").read_text())
    t = trace.load(str(DATA / f"{name}.xplane.pb"), cell.chips)
    r = run.Readings(cell, fx["facts"], t, {"compile_s": 1.25}, PEAKS)
    return cell, r


def recorded(name: str):
    """Like ``readings``, on the recordings with spans."""
    cell = run.Cell(name)
    fx = json.loads((SPANS_DATA / f"{name}.json").read_text())
    t = trace.load(str(SPANS_DATA / f"{name}.xplane.pb"), cell.chips)
    return run.Readings(cell, fx["facts"], t, {"compile_s": 1.25}, PEAKS)


def test_classify_by_kind():
    k = ('%fn.4 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %p), '
         'custom_call_target="tpu_custom_call"')
    assert trace.classify(k) == "kernel"
    assert trace.op_label(k) == "fn [kernel]"
    c = "%collective-permute-start.3 = (f32[8,8]) collective-permute-start(%a)"
    assert trace.classify(c) == "collective"
    assert trace.classify("%pad.2 = f32[8,8]{1,0} pad(f32[4,4] %x, f32[] %z)"
                          ) == "other"


def test_interval_arithmetic():
    assert trace.merge([(3, 5), (0, 2), (1, 4), (7, 7)]) == [(0, 5)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace.length(trace.subtract([(0, 4)], [(0, 4)])) == 0


@pytest.mark.parametrize("name", RECORDED)
def test_every_metric_of_the_cell_reads(name):
    r = recorded(name)
    got = run.read_per_layer(r.cell, r)
    want = {m["name"] for m in run.cell_metrics(r.cell.bench, name,
                                                "per_layer")}
    assert set(got) == want
    pinned = json.loads((SPANS_DATA / f"{name}.json").read_text())["read"]
    assert pinned and set(pinned) <= want
    for m, v in pinned.items():
        assert got[m]["value"] == pytest.approx(v, rel=1e-9), m
    for m, v in got.items():
        if v["unit"] == "%":
            assert 0 < v["value"] <= 100, (m, v)
    busy, window = r.trace.busy_s()
    assert 0 < busy <= window
    b = r.trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert any("[kernel]" in k for k, _ in b["device_ops"])


def test_sweep_readings_pinned():
    _, r = readings("star2d_r2.sweep")
    d = r.trace.devices[0]
    assert d.time("kernel") * 1e-9 == pytest.approx(0.049918353, rel=1e-6)
    got = run.read_per_layer(r.cell, r)
    assert got["kernel_roofline.sweep"]["value"] == pytest.approx(
        1.3131844, rel=1e-6)
    assert got["halo_ops_ms.sweep"]["value"] == pytest.approx(
        0.132222375, rel=1e-6)
    assert got["idle_share.sweep"]["value"] == pytest.approx(3.5007082,
                                                             rel=1e-6)


def test_serve_readings_pinned():
    _, r = readings("star2d_r2.ensemble")
    got = run.read_per_layer(r.cell, r)
    assert got["batch_fill.serve"]["value"] == pytest.approx(93.75)
    assert got["kernel_roofline.serve"]["value"] == pytest.approx(
        2.7448511, rel=1e-6)
    assert got["idle_share.serve"]["value"] == pytest.approx(78.840853,
                                                             rel=1e-6)


def test_no_trace_reads_nothing():
    cell = run.Cell("star2d_r2.sweep")
    r = run.Readings(cell, {"calls": 3}, None, {"compile_s": 1.0}, PEAKS)
    got = run.read_per_layer(cell, r)
    assert set(got) == {"compile_s"}
