"""Planned sweep: a running simulation on one chip or a mesh.

Set-up plans the configuration's problem with the default backends
(``api.plan``), compiles it (``api.compile``), makes the state on the
device from the seed and warms the executable up.  The window then calls
it in a closed loop, each call's output the next call's input, keeping
``in_flight`` calls queued on the device, until ``--seconds`` have passed
and the last call's output is ready.  The check evolves the last call's
input through the plain reference and compares the last call's output
with it (the whole chain from the seed would take the reference longer
than the window).
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, SingleDeviceSharding

from chipbench import reference, work
from chipbench.state import make_state
from chipbench.trace import WINDOW_SPAN
from repro import api
from repro.launch.mesh import make_mesh


class Path:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.devices = list(devices)
        self.steps = int(traffic["steps_per_call"])
        self.in_flight = int(traffic["in_flight"])
        self.grid = tuple(config["grid"])
        self.coeffs = np.asarray(config["stencil"]["gather_coeffs"])
        self.last_in = self.final = None

    def setup(self) -> None:
        cfg = self.config
        spec = api.from_gather_coeffs(self.coeffs,
                                      shape=cfg["stencil"]["shape"])
        m = cfg.get("mesh")
        mesh = None
        kw = {}
        if m:
            mesh = make_mesh(m["shape"], m["axes"],
                             devices=self.devices[:int(np.prod(m["shape"]))])
            kw = dict(mesh=mesh, grid_axes=tuple(m["grid_axes"]))
        problem = api.StencilProblem(spec, self.grid, dtype=cfg["dtype"],
                                     boundary=cfg["boundary"],
                                     steps=self.steps, **kw)
        self.plan = api.plan(problem)
        run = api.compile(self.plan, mesh=mesh)
        if mesh is not None:
            self.sharding = NamedSharding(mesh, run.stepper.pspec)
            self.fn = run
        else:
            self.sharding = SingleDeviceSharding(self.devices[0])
            self.fn = jax.jit(run.fn)
        self.n_devices = len(self.sharding.device_set)
        x = make_state(self.seed, self.grid, cfg["dtype"], self.sharding)
        self.x = self.fn(x).block_until_ready()   # compiles, then warms up
        print(f"sweep: {self.plan.backend} {self.plan.fuse_strategy} depth "
              f"{self.plan.fuse_depth} block {self.plan.block} schedule "
              f"{self.plan.schedule_str()} on {self.n_devices} device(s)",
              flush=True)

    def window(self, seconds: float) -> dict:
        x, self.x = self.x, None
        queue: deque = deque()
        calls = 0
        with TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                last_in, x = x, self.fn(x)
                calls += 1
                queue.append(x)
                if len(queue) >= self.in_flight:
                    queue.popleft().block_until_ready()
                if time.perf_counter() >= deadline:
                    break
            while queue:
                queue.popleft().block_until_ready()
            elapsed = time.perf_counter() - t0
        self.last_in, self.final = last_in, x
        points = int(np.prod(self.grid))
        flops, nbytes = work.call_work(self.config, points // self.n_devices,
                                       self.steps)
        return {
            "metrics": {"sweep_rate": points * self.steps * calls
                        / elapsed / 1e9},
            "attempted": calls, "failed": 0,
            "facts": {"calls": calls, "elapsed_s": elapsed,
                      "flops_per_device": flops * calls,
                      "bytes_per_device": nbytes * calls},
        }

    def release(self) -> None:
        """Nothing to free: the window keeps only what the check needs."""

    def check(self, control: bool = False) -> dict:
        """Relative gap of the last call's output to the reference from
        the same input; ``control`` puts the reference at ``high`` in the
        program's place."""
        def evolved(precision):
            return reference.evolve(self.last_in, self.coeffs, self.steps,
                                    precision, self.sharding)
        if control:
            self.final = None   # the room a mesh shard needs for both runs
        got = evolved("high") if control else self.final
        err = reference.rel_err(got, evolved(self.config["precision"]))
        return {"rel_err": err}
