"""Arrival schedules and the arithmetic of a measured window.

Times are seconds on ``time.perf_counter``.  A window request is one whose
due time falls inside the window (open loop) or one served by a step that
started inside it (closed loop).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def poisson_gaps(rate: float, seconds: float, rng: np.random.Generator):
    """Inter-arrival gaps of an open-loop Poisson stream: ``round(rate *
    seconds)`` gaps, the exponential distribution's quantiles at the mid
    points of that many equal steps, scaled to sum to ``seconds`` and put in
    an order drawn from ``rng``.  Every seed offers the same number of
    requests and the same set of gaps; only their order differs."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s offers no request")
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    return gaps[rng.permutation(n)]


def arrivals(gaps: np.ndarray) -> np.ndarray:
    """Due offsets from the window's start: the first request is due at 0,
    every later one a gap after the one before; all lie inside the window."""
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclass
class Request:
    """One window request as the driver saw it."""

    due: float
    ticket: object           # the service's SearchRequest
    submitted: float = 0.0   # when the driver called submit


@dataclass
class Step:
    """One ``SearchService.step`` call: host clock around it, and the
    number of requests it resolved ``done``."""

    t0: float
    t1: float
    served: int
    service_s: float | None = None   # the service's wall of the search


@dataclass
class Window:
    """What a measured window produced."""

    loop: str                # "open" | "closed"
    t0: float
    seconds: float
    requests: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    compiles: int = 0        # backend compiles inside the window

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def done(self) -> list:
        return [r for r in self.requests if r.ticket.status == "done"]

    def failed(self) -> int:
        """Window requests that did not resolve ``done`` with a certified
        answer."""
        return sum(r.ticket.status != "done" or r.ticket.certified is not True
                   for r in self.requests)

    def latencies_s(self) -> np.ndarray:
        """Resolution time minus due time, for every window request."""
        return np.array([r.ticket.t_done - r.due for r in self.requests
                         if r.ticket.t_done is not None], np.float64)

    def lateness_s(self) -> np.ndarray:
        """How late the driver submitted each request after it fell due."""
        return np.array([r.submitted - r.due for r in self.requests],
                        np.float64)

    def qps(self) -> float:
        """Requests resolved ``done`` per second of window.  Open loop: the
        window's requests over its length.  Closed loop: each step's served
        requests counted by the share of the step that lies inside the
        window, so a step cut by the window's edge counts pro rata."""
        if self.loop == "open":
            return len(self.done()) / self.seconds
        work = 0.0
        for s in self.steps:
            span = s.t1 - s.t0
            inside = max(0.0, min(s.t1, self.t_end) - max(s.t0, self.t0))
            if span > 0:
                work += s.served * inside / span
        return work / self.seconds


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of all values."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))
