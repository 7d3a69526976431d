"""The server's profiler spans and the kernels' stable names.

A served window under ``jax.profiler.trace`` must show every span of
``SERVE_SPANS`` in the ``.xplane.pb``, with each bucket's ``stack``,
``lookup`` and ``launch`` inside a ``turn`` and its ``launch``, ``wait``
and ``book`` linked by one ``bucket_id``.  The Pallas kernels, lowered
for the TPU on this host, carry the names a device trace labels them by.
"""
import collections
import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import api
from repro.core import stencil_spec as ss
from repro.kernels import ops
from repro.kernels.banded_mixer import banded_mixer_pallas_call
from repro.kernels.flash_attention import flash_attention_pallas
from repro.launch.serve_stencil import SERVE_SPANS


def _spans(log_dir: str) -> list[tuple[str, str, float, float, dict]]:
    """(line, name, start, end, args) of every ``stencil.`` host event."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("stencil."):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A background-stepper window of two shapes, traced after warm-up."""
    spec = ss.box(2, 1, seed=0)
    server = api.StencilServer(spec, steps=2, max_batch=4,
                               backends=["jnp"])
    rng = np.random.default_rng(3)
    states = [rng.normal(size=(16, 16) if i % 3 else (24, 24))
              .astype(np.float32) for i in range(9)]
    server.serve(states)                       # compiles every bucket
    server.reset_stats()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        server.start()
        time.sleep(0.05)     # the stepper idles, polling every 5 ms
        try:
            tickets = [server.submit(s) for s in states]
            for t in tickets:
                server.results(t, timeout_s=120.0)
        finally:
            server.stop()
    return _spans(log_dir), tickets, server.stats()


def test_every_serve_span_is_traced(served):
    spans, _, _ = served
    assert {name for _, name, *_ in spans} == set(SERVE_SPANS)


def test_bucket_spans_nest_in_their_turn(served):
    spans, _, _ = served
    turns = [(line, s, e) for line, name, s, e, _ in spans
             if name == "stencil.serve.turn"]
    inner = [sp for sp in spans if sp[1] in ("stencil.serve.stack",
                                             "stencil.serve.lookup",
                                             "stencil.serve.launch")]
    assert inner
    for line, name, s, e, _ in inner:
        assert any(tl == line and ts <= s and e <= te
                   for tl, ts, te in turns), name
    assert [a["turn"] for _, n, _, _, a in spans
            if n == "stencil.serve.turn"] == sorted(
        a["turn"] for _, n, _, _, a in spans if n == "stencil.serve.turn")


def test_each_bucket_launches_waits_and_books_once(served):
    spans, tickets, stats = served
    per = collections.Counter((name, a["bucket_id"])
                              for _, name, _, _, a in spans
                              if "bucket_id" in a)
    ids = {b for _, b in per}
    for b in ids:
        for name in ("stencil.serve.stack", "stencil.serve.lookup",
                     "stencil.serve.launch", "stencil.serve.wait",
                     "stencil.serve.book"):
            assert per[(name, b)] == 1, (name, b)
    launches = [a for _, name, _, _, a in spans
                if name == "stencil.serve.launch"]
    assert len(launches) == len(ids) == stats["batches"]
    # every request rides exactly one launch, named by its ticket
    sent = [int(t) for a in launches for t in str(a["tickets"]).split()]
    assert sorted(sent) == sorted(tickets)
    assert sum(int(a["requests"]) for a in launches) == stats["requests"]


SPEC_2D = ss.PAPER_SUITE()["star2d_r2"]
BAND = np.linspace(0.1, 0.4, 4, dtype=np.float32)


@pytest.mark.parametrize("kernel,fn,shapes", [
    ("stencil_step", lambda x: ops.stencil_matrixized(
        x, spec=SPEC_2D, block=(128, 128), boundary="periodic",
        interpret=False), [(256, 256)]),
    ("stencil_sweep", lambda x: ops.stencil_sweep_matrixized(
        x, spec=SPEC_2D, steps=4, block=(128, 128), boundary="periodic",
        interpret=False), [(256, 256)]),
    ("banded_mixer", lambda x: banded_mixer_pallas_call(
        x, BAND, interpret=False), [(256, 128)]),
    ("flash_attention", lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False), [(1, 2, 256, 128)] * 3),
])
def test_kernels_carry_stable_names(kernel, fn, shapes):
    """Lowered for the TPU on this host, each Pallas kernel is the Mosaic
    custom call named ``kernel``, with no shape or depth in the name."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{kernel}"' in text
