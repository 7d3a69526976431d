"""The served cell's requests: every seed gets the same work, reordered."""
import numpy as np

from chipbench import run
from chipbench.paths import serve

TRAFFIC = run.Cell("star2d_r2.ensemble").traffic


def test_same_sizes_and_gaps_for_every_seed():
    a = serve.schedule(TRAFFIC, 10.0, 2**33 + 1, rate=320)
    b = serve.schedule(TRAFFIC, 10.0, 7, rate=320)
    n = 3200
    assert len(a["due"]) == len(b["due"]) == n
    # the same gaps (the last is never waited for), in another order
    ga, gb = np.sort(np.diff(a["due"])), np.sort(np.diff(b["due"]))
    assert np.isin(np.round(ga, 9), np.round(gb, 9)).sum() >= n - 2
    assert np.bincount(a["grid"]).tolist() == np.bincount(b["grid"]).tolist()
    assert not np.array_equal(a["grid"], b["grid"])
    assert a["due"][0] == 0 and np.all(np.diff(a["due"]) > 0)
    assert a["due"][-1] < 10.0


def test_every_block_holds_the_shares():
    s = serve.schedule(TRAFFIC, 10.0, 3)
    assert s["due"] is None
    block = TRAFFIC["block"]
    assert len(s["grid"]) >= TRAFFIC["clients"] + 1000 * 10
    want = [round(g["share"] * block) for g in TRAFFIC["grids"]]
    per_block = s["grid"][:len(s["grid"]) // block * block].reshape(-1, block)
    for row in per_block[:500]:
        assert np.bincount(row, minlength=len(want)).tolist() == want
    assert len({tuple(row) for row in per_block[:500]}) > 1
    assert s["pool"].max() < TRAFFIC["pool"]


def test_sample_is_uniform_and_bounded():
    hits = np.zeros(100)
    for seed in range(2000):
        sample = serve.Sample(8, np.random.default_rng(seed))
        for k in range(100):
            sample.offer(k)
        assert len(sample.items) == 8 and len(set(sample.items)) == 8
        hits[sample.items] += 1
    # each of 100 items lands in a sample of 8 with probability 0.08
    assert abs(hits[:50].sum() - hits[50:].sum()) < 0.1 * hits.sum()
    few = serve.Sample(8, np.random.default_rng(1))
    for k in range(3):
        few.offer(k)
    assert few.items == [0, 1, 2]


def test_rate_override():
    s = serve.schedule(TRAFFIC, 4.0, 3, rate=50)
    assert len(s["due"]) == 200
