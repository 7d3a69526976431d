"""The server's own spans, read on the device trace's clock.

``StencilServer`` wraps its scheduler turn in ``stencil.serve.*`` spans
(``SERVE_SPANS`` in ``repro/launch/serve_stencil.py``); the profiler
writes them into the same ``.xplane.pb`` as the device ops, on the same
clock, and :func:`chipbench.trace.load` keeps them among ``Trace.host``.
Spans are matched by name, never by the line they sit on: the profiler
names a Python thread's line ``python``, ``python3`` or nothing.

  turn    one scheduler turn; ``stack``, ``lookup``, ``launch`` (a
          bucket's host work) and ``wait``, ``book`` (settling a bucket
          of an earlier turn) run inside it
  idle    the background stepper waiting for work: the clients' turn

The host work of a turn is its time outside ``wait``.  A trace with no
such span (the sweep, or a program without them) reads nothing.
"""
from __future__ import annotations

import bisect
import statistics

from chipbench.trace import length, merge, subtract

PREFIX = "stencil.serve."
TURN, STACK, LOOKUP, LAUNCH, WAIT, BOOK, IDLE = (
    PREFIX + s for s in ("turn", "stack", "lookup", "launch", "wait", "book",
                         "idle"))
#: the spans of a bucket's host work inside a turn
CHILDREN = (STACK, LOOKUP, LAUNCH, BOOK)
#: the parts of :func:`parts` that make up a turn's host work
IN_TURN = tuple(c[len(PREFIX):] for c in CHILDREN) + ("turn, other",)
#: a wait at least this long blocked on the device (clock check)
BLOCKING_WAIT_NS = 0.5e6


def events(trace, name: str) -> list[tuple[float, float]]:
    """(start, end) of every host event called ``name``, clipped to the
    window, in start order."""
    w0, w1 = trace.window
    return sorted((max(s, w0), min(e, w1)) for _, n, s, e in trace.host
                  if n == name and e > w0 and s < w1)


def intersect(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` within merged intervals ``b``."""
    return subtract(a, subtract(a, b))


def launches(trace) -> int:
    """Buckets dispatched in the window: ``launch`` spans starting in it."""
    w0, w1 = trace.window
    return sum(1 for _, n, s, _ in trace.host
               if n == LAUNCH and w0 <= s < w1)


def parts(trace) -> dict[str, list[tuple[float, float]]]:
    """The window cut into disjoint parts by the innermost stepper span:
    each child, the rest of the turn, ``wait``, ``idle`` and no span."""
    left = [trace.window]
    out = {}
    for key, name in ([("wait", WAIT)]
                      + [(c[len(PREFIX):], c) for c in CHILDREN]
                      + [("turn, other", TURN), ("idle", IDLE)]):
        out[key] = intersect(left, merge(events(trace, name)))
        left = subtract(left, out[key])
    out["no span"] = left
    return out


def idle_split(trace) -> dict[str, float]:
    """Idle share of the window on the worst device, in %, by the part of
    :func:`parts` the host was in; the values sum to its idle share."""
    gaps = max((trace.idle_gaps(d) for d in trace.devices), key=length)
    w = trace.window[1] - trace.window[0]
    return {k: 100.0 * length(intersect(gaps, iv)) / w
            for k, iv in parts(trace).items()}


def clock_check(trace) -> tuple[int, float, float, float] | None:
    """For each wait of at least 0.5 ms: the gap from the end of the
    device op that ends nearest to it to its end, in µs (positive: the
    wait ended after the op).  Returns (waits, median, least, most gap);
    a blocking wait should end just after its bucket's last op."""
    ends = sorted(o.end for d in trace.devices for o in d.ops)
    gaps = []
    for s, e in events(trace, WAIT):
        if e - s < BLOCKING_WAIT_NS or not ends:
            continue
        j = bisect.bisect_left(ends, e)
        near = min((ends[k] for k in (j - 1, j) if 0 <= k < len(ends)),
                   key=lambda t: abs(e - t))
        gaps.append((e - near) * 1e-3)
    if not gaps:
        return None
    return len(gaps), statistics.median(gaps), min(gaps), max(gaps)


def host_turn_ms(r) -> float | None:
    """Host work per bucket dispatched in the window: the turns less their
    waits, over the ``launch`` spans, in ms."""
    if r.trace is None or not (n := launches(r.trace)):
        return None
    per = {k: length(iv) * 1e-6 / n for k, iv in parts(r.trace).items()
           if k in IN_TURN}
    total = sum(per.values())
    print(f"host turn per bucket ({n} buckets): {total:.4f} ms = "
          + " + ".join(f"{k} {v:.4f}" for k, v in per.items()), flush=True)
    check = clock_check(r.trace)
    if check is not None:
        print(f"clock check: {check[0]} waits of 0.5 ms or more end "
              f"{check[1]:.3f} us after the nearest device op's end at the "
              f"median ({check[2]:.3f} to {check[3]:.3f} us)", flush=True)
    return total


def idle_in_turn(r) -> float | None:
    """Share of the window with no op on the device while the stepper was
    in a turn's host work (worst device), in %."""
    if r.trace is None or not events(r.trace, TURN):
        return None
    split = idle_split(r.trace)
    print("idle split (% of the window): "
          + ", ".join(f"{k} {v:.4f}" for k, v in split.items())
          + f"; total {sum(split.values()):.4f}", flush=True)
    return sum(split[k] for k in IN_TURN)
