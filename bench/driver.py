"""Set-up of a cell's service and the loops that offer it load.

One process, one thread.  The open loop submits every request that has come
due with ``submit(q, now=due)``, so a request's latency counts from when it
was due, and then calls ``step()``; it sleeps only when nothing is queued.
The closed loop keeps a fixed number of requests outstanding.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

from bench.tracing import span
from bench.window import Request, Step, Window, arrivals, poisson_gaps


class CompileClock:
    """Counts backend compiles reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.compiles += 1
            self.seconds += duration

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def open_service(cfg: dict, traffic: dict, X: np.ndarray, seed: int,
                 devs: list):
    """The program's own served index over ``X``, as the configuration
    states it: ``open_index(..., backend="jax", serving=True)``.  On more
    than one chip the corpus rows are divided over ``devs``, the cell's
    chips (``open_index(mesh=)``, a one-axis ``"data"`` mesh)."""
    from jax.sharding import Mesh

    from repro.api import SchedulePolicy, open_index
    mesh = Mesh(np.asarray(devs), ("data",)) if len(devs) > 1 else None
    return open_index(X, index=cfg["index"], method=cfg["method"],
                      backend="jax", schedule=SchedulePolicy(**cfg["policy"]),
                      seed=seed, serving=True, mesh=mesh,
                      serving_params={"slots": int(traffic["slots"]),
                                      "k": int(cfg["k"])})


def warm_up(svc, pools: dict, rounds: int = 2) -> None:
    """Serve ``rounds`` full batches of every query pool, so that every
    program a window can run (the adaptive policy's full-scan and switching
    bodies among them) is compiled and loaded before it starts."""
    for pool in pools.values():
        for r in range(rounds):
            for j in range(svc.slots):
                svc.submit(pool[(r * svc.slots + j) % len(pool)])
            svc.step()


def _step(t0: float, res: list) -> Step:
    return Step(t0, time.perf_counter(), sum(r.status == "done" for r in res),
                res[-1].service_s if res else None)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left - 2e-4 if left > 1e-3 else 0)


def open_loop(svc, pool: np.ndarray, rate: float, seconds: float,
              rng: np.random.Generator, *, trace: bool = False,
              lead_s: float = 0.05) -> Window:
    """Offer ``rate`` requests/s for ``seconds`` (Poisson, fixed count) and
    serve until every window request has resolved."""
    offs = arrivals(poisson_gaps(rate, seconds, rng))
    qidx = rng.integers(0, len(pool), len(offs))
    w = Window("open", time.perf_counter() + lead_s, seconds)
    due = w.t0 + offs
    n, i = len(due), 0
    while i < n or svc.pending:
        now = time.perf_counter()
        if i < n and due[i] <= now:
            with span("bench.submit", trace):
                while i < n and due[i] <= now:
                    t = svc.submit(pool[qidx[i]], now=float(due[i]))
                    w.requests.append(Request(float(due[i]), t, now))
                    i += 1
        if svc.pending:
            with span("bench.step", trace, queries=min(svc.pending,
                                                        svc.slots)):
                t0 = time.perf_counter()
                res = svc.step()
                w.steps.append(_step(t0, res))
        elif i < n:
            with span("bench.idle_wait", trace):
                _sleep_until(float(due[i]))
    return w


def closed_loop(svc, pool: np.ndarray, outstanding: int, seconds: float,
                rng: np.random.Generator, *, trace: bool = False) -> Window:
    """Keep ``outstanding`` requests queued and step until ``seconds``
    have passed.  Window requests are those served by steps that started
    inside the window; what is still queued at its end is dropped."""
    w = Window("closed", time.perf_counter(), seconds)
    queued: deque = deque()
    while True:
        now = time.perf_counter()
        if now >= w.t_end:
            break
        if len(queued) < outstanding:
            with span("bench.submit", trace):
                qidx = rng.integers(0, len(pool), outstanding - len(queued))
                for j in qidx:
                    queued.append(Request(now, svc.submit(pool[j], now=now),
                                          now))
        with span("bench.step", trace, queries=min(svc.pending, svc.slots)):
            t0 = time.perf_counter()
            res = svc.step()
            w.steps.append(_step(t0, res))
        for _ in range(len(res)):
            w.requests.append(queued.popleft())
    return w


def run_window(svc, pool, traffic: dict, seconds: float,
               rng: np.random.Generator, *, trace: bool = False) -> Window:
    """The traffic mix's loop for ``seconds``."""
    if traffic["loop"] == "open":
        return open_loop(svc, pool, float(traffic["rate_qps"]), seconds, rng,
                         trace=trace)
    if traffic["loop"] == "closed":
        return closed_loop(svc, pool, int(traffic["outstanding"]), seconds,
                           rng, trace=trace)
    raise ValueError(f"traffic loop must be 'open' or 'closed', got "
                     f"{traffic['loop']!r}")
