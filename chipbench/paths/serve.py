"""Served path: many small independent simulations share one chip.

Set-up builds a ``StencilServer`` as the traffic file asks, makes a pool
of states of each grid on the device from the seed, and serves every
(grid, bucket) pair the traffic can form once, so the window compiles
nothing.  The window sends requests in one of two loops:

  closed  ``clients`` callers (a parameter study keeping that many
          simulations outstanding) each send their next request as soon
          as their last one returns, until ``--seconds`` have passed;
  open    requests are due at fixed offsets at ``rate_per_s`` (or the
          rate given), and a generator thread sends each when due.

One collector thread per grid claims its results in order (FIFO per grid,
as the server settles them) and holds each on the host side of
``block_until_ready``.  A request's latency runs from when it was due
(open) or sent (closed).  Every seed gets the same work in another order:
each block of ``block`` requests holds the grids' shares exactly.  The
check compares a uniform sample of the served results, drawn from the
seed, against the plain reference evolved from the same pool states.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from chipbench import reference, work
from chipbench.state import make_state
from chipbench.trace import WINDOW_SPAN
from repro import api

#: more requests per second than one chip completes: the closed loop's
#: stream of requests never runs dry
MOST_PER_S = 20000


def mix(traffic: dict, n: int, rng) -> np.ndarray:
    """Grid index of each of ``n`` requests: every block of ``block``
    requests holds each grid's share (largest remainder), shuffled."""
    p = np.array([g["share"] for g in traffic["grids"]], float)
    block = int(traffic["block"])
    counts = np.floor(p / p.sum() * block).astype(int)
    order = np.argsort(-(p / p.sum() * block - counts), kind="stable")
    counts[order[:block - counts.sum()]] += 1
    base = np.repeat(np.arange(len(p)), counts)
    blocks = np.tile(base, (-(-n // block), 1))
    return rng.permuted(blocks, axis=1).ravel()[:n]


def schedule(traffic: dict, seconds: float, seed: int, rate=None) -> dict:
    """Grid and pool index of every request a window can send and, in an
    open loop (``rate_per_s`` in the traffic, or ``rate``), the offset at
    which each is due: the midpoint quantiles of an exponential at the
    rate, shuffled, so every seed gets the same gaps."""
    rng = np.random.default_rng([int(seed), 1])
    rate = traffic.get("rate_per_s") if rate is None else rate
    if rate is None:
        n = int(traffic["clients"]) + int(np.ceil(MOST_PER_S * seconds))
        due = None
    else:
        rate = float(rate)
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        rng.shuffle(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return {"due": due, "grid": mix(traffic, n, rng),
            "pool": rng.integers(0, int(traffic["pool"]), size=n)}


class Sample:
    """A uniform sample of at most ``size`` of the items offered, drawn
    from ``rng`` (reservoir sampling)."""

    def __init__(self, size: int, rng):
        self.size, self.rng = int(size), rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        elif (j := int(self.rng.integers(0, self.seen))) < self.size:
            self.items[j] = item


class Path:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.devices = list(devices)
        self.steps = int(traffic["steps"])
        self.grids = [tuple(g["grid"]) for g in traffic["grids"]]
        self.coeffs = np.asarray(config["stencil"]["gather_coeffs"])
        self.kept: list[Sample] = []

    def setup(self) -> None:
        cfg, tf = self.config, self.traffic
        spec = api.from_gather_coeffs(self.coeffs,
                                      shape=cfg["stencil"]["shape"])
        self.server = api.StencilServer(
            spec, self.steps, boundary=cfg["boundary"], dtype=cfg["dtype"],
            max_batch=int(tf["max_batch"]), admission=bool(tf["admission"]),
            fallback_after=None, devices=[self.devices[0]])
        self.pools = []
        sharding = jax.sharding.SingleDeviceSharding(self.devices[0])
        for g, shape in enumerate(self.grids):
            block = make_state(self.seed, (int(tf["pool"]),) + shape,
                               cfg["dtype"], sharding, salt=g + 1)
            self.pools.append([block[i] for i in range(block.shape[0])])
        caps = {}
        for g, shape in enumerate(self.grids):
            caps[shape] = cap = self.server.bucket_cap(shape)
            for n in range(1, cap + 1):
                jax.block_until_ready(self.server.serve(self.pools[g][:n]))
        self.caps = caps
        print(f"serve: buckets warmed for grids {self.grids} with caps "
              f"{[caps[s] for s in self.grids]}", flush=True)

    def window(self, seconds: float, rate=None) -> dict:
        tf = self.traffic
        sched = schedule(tf, seconds, self.seed, rate)
        self.sched = sched
        closed = sched["due"] is None
        n = len(sched["grid"])
        sent = np.full(n, np.nan)
        done = np.full(n, np.nan)
        failed = np.zeros(n, bool)
        drain = float(tf["drain_s"])
        server = self.server
        server.reset_stats()
        misses0 = server.stats()["plan_cache"]["misses"]
        queues = [queue.Queue() for _ in self.grids]
        per = int(tf["check_sample"]) // len(self.grids)
        self.kept = [Sample(per, np.random.default_rng([self.seed, 2, g]))
                     for g in range(len(self.grids))]
        lock = threading.Lock()
        cursor, clients = [0], [int(tf["clients"]) if closed else 0]
        t0 = close = 0.0

        def send(k: int) -> bool:
            g = int(sched["grid"][k])
            sent[k] = time.perf_counter()
            try:
                ticket = server.submit(self.pools[g][sched["pool"][k]])
            except Exception:              # a refused request has failed
                failed[k] = True
                return False
            queues[g].put((k, ticket))
            return True

        def finish() -> None:              # the collectors always finish
            for q in queues:
                q.put(None)

        def client_turn() -> None:
            """A closed-loop client sends its next request, or leaves."""
            while time.perf_counter() < close:
                with lock:
                    k, cursor[0] = cursor[0], cursor[0] + 1
                if k >= n:
                    print("serve: the stream of requests ran dry", flush=True)
                    break
                if send(k):
                    return
            with lock:
                clients[0] -= 1
                last = clients[0] == 0
            if last:
                finish()

        def generate() -> None:
            try:
                for k in range(n):
                    due = t0 + sched["due"][k]
                    while (wait := due - time.perf_counter()) > 0:
                        time.sleep(wait)
                    send(k)
            finally:
                finish()

        def collect(g: int) -> None:
            while (item := queues[g].get()) is not None:
                k, ticket = item
                try:
                    left = close + drain - time.perf_counter()
                    res = server.results(ticket, timeout_s=max(left, 1e-3))
                    res.block_until_ready()
                    done[k] = time.perf_counter()
                    self.kept[g].offer((k, res))
                except Exception:      # failed, shed or never came
                    failed[k] = True
                if closed:
                    client_turn()

        server.start()
        threads = [threading.Thread(target=collect, args=(g,), daemon=True)
                   for g in range(len(self.grids))]
        try:
            with TraceAnnotation(WINDOW_SPAN):
                t0 = time.perf_counter()
                close = t0 + seconds
                for t in threads:
                    t.start()
                if closed:
                    for _ in range(clients[0]):
                        client_turn()
                else:
                    gen = threading.Thread(target=generate, daemon=True)
                    gen.start()
                    gen.join()
                for t in threads:
                    t.join()
        finally:
            server.stop()
        st = server.stats()

        # every request of an open loop is attempted; a closed loop's
        # stream runs past what its clients sent
        tried = ~np.isnan(sent) if closed else np.ones(n, bool)
        failed |= tried & np.isnan(done)   # never sent or never came
        start = sent if closed else t0 + sched["due"]
        lat = np.where(failed, seconds + drain, done - start)[tried]
        completed = int(np.sum(tried & ~failed & (done <= close)))
        lag_ms = (sent - start)[tried] * 1e3
        lat_ms = {q: float(np.percentile(lat, q)) * 1e3 for q in (50, 90, 95)}
        loop = (f"from {int(tf['clients'])} clients" if closed else
                f"due at {n / seconds:.1f}/s (generator lag p50 "
                f"{np.median(lag_ms):.3f} ms, p95 "
                f"{np.percentile(lag_ms, 95):.3f} ms, max "
                f"{lag_ms.max():.3f} ms)")
        print(f"serve: {int(tried.sum())} requests {loop}, {completed} "
              f"completed in the window, {int(failed.sum())} failed; "
              f"latency p50 {lat_ms[50]:.4f} p90 {lat_ms[90]:.4f} p95 "
              f"{lat_ms[95]:.4f} max {lat.max() * 1e3:.4f} ms", flush=True)
        flops = nbytes = 0
        for g, shape in enumerate(self.grids):
            served = int(np.sum((sched["grid"] == g) & tried & ~failed))
            f, b = work.call_work(self.config, int(np.prod(shape)),
                                  self.steps)
            flops, nbytes = flops + f * served, nbytes + b * served
        first = (start - t0 < seconds / 2)[tried]
        return {
            "metrics": {"serve_p50_ms": lat_ms[50], "serve_p90_ms": lat_ms[90],
                        "serve_p95_ms": lat_ms[95],
                        "serve_rate": completed / seconds},
            "attempted": int(tried.sum()), "failed": int(failed.sum()),
            "facts": {"requests": st["requests"], "batches": st["batches"],
                      "padded_states": st["padded_states"],
                      "plan_cache_misses":
                          st["plan_cache"]["misses"] - misses0,
                      "flops_per_device": flops, "bytes_per_device": nbytes,
                      "lag_p95_ms": float(np.percentile(lag_ms, 95)),
                      "p95_ms_by_half": [
                          float(np.percentile(lat[h], 95)) * 1e3
                          if h.any() else None for h in (first, ~first)]},
        }

    def release(self) -> None:
        """Frees the server's executables; the pools and the kept sample
        stay for the check."""
        self.server.cache.clear()

    def check(self, control: bool = False) -> dict:
        """Largest relative gap of a sampled result to the reference;
        ``control`` puts the reference at ``high`` in the program's place.
        A grid with no result in the sample reads nothing."""
        worst = 0.0
        prec = self.config["precision"]
        for g, sample in enumerate(self.kept):
            if not sample.items:
                return {"rel_err": None}
            ks = [k for k, _ in sample.items]
            x = jnp.stack([self.pools[g][self.sched["pool"][k]] for k in ks])
            ref = reference.evolve(x, self.coeffs, self.steps, prec)
            got = (reference.evolve(x, self.coeffs, self.steps, "high")
                   if control else [res for _, res in sample.items])
            for i in range(len(ks)):
                worst = max(worst, reference.rel_err(got[i], ref[i]))
        return {"rel_err": worst}
