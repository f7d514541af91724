"""Drive the serving engine for one measured window, from one thread.

The loop is what a server's pump does: submit every request that is due,
then ``Engine.step()``, again and again. Each output token is stamped
with the host clock where the engine emits it (its ``on_token`` hook), so
a token's time is the moment the host holds it; an open-loop request is
timed from when it was due, so a stalled step delays the requests behind
it as it would a user's. One thread, so the generator never waits on a
lock the pump holds (``EngineServer``'s pump re-takes its lock between
steps and starves a submitting thread; see PERF.md).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional

import numpy as np

from harness.traffic import Req, Traffic

GRACE_S = 60.0      # after the window: wait this long at most for answers


@dataclasses.dataclass
class Flight:
    req: Req
    uid: int
    due: float                    # absolute host time it was due
    sent: float                   # absolute host time submit() returned
    matched: Optional[int] = None  # prefix-cache tokens at admission
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.out_len


class Window:
    """One run's traffic through one warmed ``Engine``."""

    def __init__(self, eng, traffic: Traffic, greedy_params):
        self.eng = eng
        self.traffic = traffic
        self.params = greedy_params      # out_len -> SamplingParams
        self.flights: Dict[int, Flight] = {}
        self.completed: List[int] = []
        self.next_req = 0
        self._uids = itertools.count(1)

        def on_token(uid, token, info=None):
            f = self.flights.get(uid)
            if f is not None:
                f.times.append(time.perf_counter())
                f.tokens.append(int(token))
                if f.done:
                    self.completed.append(uid)

        eng.on_token = on_token
        inner_admit = eng._admit_paged

        def admit(budget):
            # each admission's prefix-cache hit, as the engine recorded it
            out = inner_admit(budget)
            if out is not None:
                for job in eng._prefill_jobs.values():
                    f = self.flights.get(job.req.uid)
                    if f is not None and f.matched is None:
                        f.matched = job.matched_tokens
            return out

        eng._admit_paged = admit

    # ---------------------------------------------------------------- send
    def send(self, req: Req, due: float) -> Flight:
        from repro.serve import Request
        uid = next(self._uids)
        f = Flight(req=req, uid=uid, due=due, sent=0.0)
        self.flights[uid] = f
        self.eng.submit(Request(uid=uid, prompt=req.prompt,
                                params=self.params(req.out_len)))
        f.sent = time.perf_counter()
        return f

    def take(self) -> Req:
        r = self.traffic.requests[self.next_req]
        self.next_req += 1
        return r

    def step_until(self, ready, limit_s: float) -> bool:
        """Step the engine until ``ready()`` or ``limit_s`` passes."""
        end = time.perf_counter() + limit_s
        while not ready():
            if time.perf_counter() >= end or not self.eng.sched.has_work:
                return ready()
            self.eng.step()
        return True

    def first_tokens(self, flights: List[Flight], limit_s: float) -> bool:
        return self.step_until(lambda: all(f.tokens for f in flights),
                               limit_s)

    # ---------------------------------------------------------- closed loop
    def start_closed(self) -> List[Flight]:
        """Send every client's first request and step until each has its
        first token (set-up: the window opens on busy lanes)."""
        now = time.perf_counter()
        first = [self.send(self.take(), now)
                 for _ in range(self.traffic.spec["clients"])]
        if not self.first_tokens(first, GRACE_S * 5):
            raise RuntimeError("closed-loop start: a first request never "
                               "produced its first token")
        return first

    def run_closed(self, seconds: float) -> tuple:
        """Each client sends its next request as soon as its answer is
        complete, between steps."""
        t0 = time.perf_counter()
        t1 = t0 + seconds
        while time.perf_counter() < t1:
            done, self.completed = self.completed, []
            now = time.perf_counter()
            for _ in done:
                self.send(self.take(), now)
            self.eng.step()
        return t0, t1

    # ------------------------------------------------------------ open loop
    def run_open(self, seconds: float, preroll_s: float) -> tuple:
        """Send every request due in [-preroll, seconds) once it is due;
        the window is [t0, t0 + seconds)."""
        reqs = self.traffic.requests
        t0 = time.perf_counter() + preroll_s
        t1 = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            while (self.next_req < len(reqs)
                   and t0 + reqs[self.next_req].due <= now):
                r = self.take()
                self.send(r, t0 + r.due)
            if self.eng.sched.has_work:
                self.eng.step()
                continue
            nxt = t0 + reqs[self.next_req].due if self.next_req < len(reqs) \
                else t1
            time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
        return t0, t1

    # --------------------------------------------------------------- after
    def in_window(self, t0: float, t1: float) -> List[Flight]:
        """Requests due in the window (closed loop: sent in it)."""
        return [f for f in self.flights.values() if t0 <= f.due < t1]

    def active_in(self, t0: float, t1: float) -> List[Flight]:
        """Requests the window worked on: sent before it closed and not
        finished before it opened."""
        return [f for f in self.flights.values() if f.due < t1
                and not (f.done and f.times and f.times[-1] < t0)]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def token_gaps(flights: List[Flight], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive output tokens of a request, for every gap
    that ends inside the window."""
    gaps = []
    for f in flights:
        ts = np.asarray(f.times)
        if len(ts) < 2:
            continue
        d = np.diff(ts)
        end = ts[1:]
        gaps.extend(d[(end >= t0) & (end < t1)].tolist())
    return gaps


def tokens_in(flights: List[Flight], t0: float, t1: float) -> int:
    n = 0
    for f in flights:
        ts = np.asarray(f.times)
        n += int(np.sum((ts >= t0) & (ts < t1)))
    return n
