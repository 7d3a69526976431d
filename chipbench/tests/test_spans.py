"""The readers of the server's spans, and the named kernels: on the
recordings of this program, on synthetic intervals, and on traces that
have no such span.

``data/spans/<workload>.xplane.pb`` and ``.json`` were recorded on a TPU
v5e by ``record.py``, at the cut sizes of ``data/`` (the recordings
``test_reducers.py`` reads, made before the program had spans or kernel
names); the numbers pinned here were read from them then.
"""
import pytest

from chipbench import reduce, run, spans, trace
from chipbench.tests.test_reducers import (PEAKS, RECORDED, readings,
                                           recorded)

MS = 1e6                                  # ns
HOST_TURN_MS = 4.9360714
IDLE_IN_TURN = 67.727651


@pytest.mark.parametrize("name", RECORDED)
def test_every_metric_of_the_cell_reads_with_spans(name):
    # the span readers read where the server's spans are, and the device
    # idles in the turn's host work no longer than in the whole window
    r = recorded(name)
    if spans.launches(r.trace):
        assert spans.host_turn_ms(r) > 0
        assert 0 <= spans.idle_in_turn(r) <= reduce.idle_share(r)
    else:
        assert spans.host_turn_ms(r) is None
        assert spans.idle_in_turn(r) is None


@pytest.mark.parametrize("name", ["star2d_r2.sweep",
                                  "star2d_r2.ensemble"])
def test_breakdown_names_the_kernels(name):
    # both paths run the in-kernel sweep: 8- and 16-step calls
    labels = [k for k, _ in recorded(name).trace.breakdown()["device_ops"]]
    assert "stencil_sweep [kernel]" in labels
    assert "fn [kernel]" not in labels


def test_sweep_readings_with_spans_pinned():
    r = recorded("star2d_r2.sweep")
    d = r.trace.devices[0]
    assert d.time("kernel") * 1e-9 == pytest.approx(0.053173742, rel=1e-6)
    got = run.read_per_layer(r.cell, r)
    assert got["kernel_roofline.sweep"]["value"] == pytest.approx(
        1.3098383, rel=1e-6)
    assert got["halo_ops_ms.sweep"]["value"] == pytest.approx(
        0.13251376, rel=1e-6)
    assert got["idle_share.sweep"]["value"] == pytest.approx(2.9065969,
                                                             rel=1e-6)


def test_serve_readings_with_spans_pinned():
    r = recorded("star2d_r2.ensemble")
    got = run.read_per_layer(r.cell, r)
    assert got["batch_fill.serve"]["value"] == pytest.approx(30 / 31 * 100)
    assert got["kernel_roofline.serve"]["value"] == pytest.approx(
        2.8507379, rel=1e-6)
    assert got["idle_share.serve"]["value"] == pytest.approx(81.045264,
                                                             rel=1e-6)


def test_readers_on_the_served_recording_pinned(capsys):
    r = recorded("star2d_r2.ensemble")
    assert spans.launches(r.trace) == r.facts["batches"]
    assert spans.host_turn_ms(r) == pytest.approx(HOST_TURN_MS, rel=1e-6)
    assert spans.idle_in_turn(r) == pytest.approx(IDLE_IN_TURN, rel=1e-6)
    out = capsys.readouterr().out
    assert "host turn per bucket" in out and "idle split" in out
    split = spans.idle_split(r.trace)
    assert sum(split.values()) == pytest.approx(reduce.idle_share(r),
                                                rel=1e-9)
    assert sum(split[k] for k in ("stack", "lookup", "launch", "book",
                                  "turn, other")) == pytest.approx(
        IDLE_IN_TURN, rel=1e-6)


def synthetic() -> trace.Trace:
    """A 100 ms window: the device busy in [10, 20] and [50, 60]; two
    turns, each one bucket and one wait; the stepper idle in [40, 48].
    The gap [20, 50] straddles the first turn's end and the idle span."""
    ops = [trace.Op(10 * MS, 20 * MS, "kernel", "stencil_step [kernel]"),
           trace.Op(50 * MS, 60 * MS, "kernel", "stencil_step [kernel]")]
    host = [("python3", trace.WINDOW_SPAN, 0, 100 * MS)]
    for name, s, e in [
            ("turn", 5, 40), ("stack", 6, 12), ("launch", 12, 14),
            ("wait", 15, 30), ("book", 30, 35), ("idle", 40, 48),
            ("turn", 48, 95), ("stack", 49, 52), ("launch", 52, 53),
            ("wait", 55, 62), ("book", 62, 65)]:
        host.append(("", spans.PREFIX + name, s * MS, e * MS))
    return trace.Trace((0, 100 * MS), [trace.Device("/device:TPU:0", ops)],
                       host)


def test_readers_on_synthetic_intervals():
    t = synthetic()
    r = run.Readings(run.Cell("star2d_r2.ensemble"), {}, t, {}, PEAKS)
    # host work: [5, 15] + [30, 40] + [48, 55] + [62, 95] = 60 ms, 2 buckets
    assert spans.host_turn_ms(r) == pytest.approx(30.0)
    # idle gaps [0, 10], [20, 50], [60, 100] within that work: 5 + 12 + 33
    assert spans.idle_in_turn(r) == pytest.approx(50.0)
    assert spans.idle_split(t) == pytest.approx({
        "wait": 12.0, "stack": 5.0, "lookup": 0.0, "launch": 0.0,
        "book": 8.0, "turn, other": 37.0, "idle": 8.0, "no span": 10.0})
    assert reduce.idle_share(r) == pytest.approx(80.0)
    # both waits end after the nearest op's end: by 10 ms and by 2 ms
    assert spans.clock_check(t) == (2, pytest.approx(6000.0),
                                    pytest.approx(2000.0),
                                    pytest.approx(10000.0))


def test_spans_outside_the_window_are_clipped():
    t = synthetic()
    t.host.append(("", spans.TURN, 90 * MS, 130 * MS))
    t.host.append(("", spans.LAUNCH, 101 * MS, 102 * MS))
    assert spans.launches(t) == 2
    assert spans.events(t, spans.TURN)[-1] == (90 * MS, 100 * MS)


def test_no_serve_span_reads_nothing():
    t = synthetic()
    t.host = [h for h in t.host if not h[1].startswith(spans.PREFIX)]
    r = run.Readings(run.Cell("star2d_r2.ensemble"), {}, t, {}, PEAKS)
    assert spans.host_turn_ms(r) is None
    assert spans.idle_in_turn(r) is None
    # the sweep, and a served recording of a program without spans
    for name in ("star2d_r2.sweep", "star2d_r2.ensemble"):
        _, old = readings(name)
        assert spans.host_turn_ms(old) is None
        assert spans.idle_in_turn(old) is None
        assert spans.clock_check(old.trace) is None
    sweep = recorded("star2d_r2.sweep")
    assert spans.host_turn_ms(sweep) is None
    assert spans.idle_in_turn(sweep) is None
