"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

A trace is read once into plain lists of events (name, start, duration in
seconds). Device events are the operations on the TPU's op line; host
events are the benchmark's own annotations (``engine_step`` around each
``Engine.step``, ``decode_dispatch`` and ``prefill_dispatch`` around the
engine's two step programs).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("decode_dispatch", "prefill_dispatch", "engine_step")


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Ev]]        # device index -> leaf operations
    modules: Dict[int, List[Ev]]    # device index -> program executions
    host: List[Ev]                  # benchmark annotations


def _device_index(plane_name: str) -> int:
    m = re.search(r"(\d+)$", plane_name)
    return int(m.group(1)) if m else 0


def from_profile(pd) -> Trace:
    """From a ``jax.profiler.ProfileData`` (or anything with its shape:
    ``planes`` with ``name`` and ``lines``, lines with ``name`` and
    ``events``, events with ``name``, ``start_ns``, ``duration_ns``)."""
    ops: Dict[int, List[Ev]] = {}
    modules: Dict[int, List[Ev]] = {}
    host: List[Ev] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = _device_index(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = ops.setdefault(dev, [])
                elif line.name == "XLA Modules":
                    dst = modules.setdefault(dev, [])
                else:
                    continue
                dst.extend(Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(ops=ops, modules=modules, host=host)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(events: Sequence[Ev]) -> float:
    """Seconds in which at least one of ``events`` ran."""
    return sum(e - s for s, e in union((x.start, x.end) for x in events))


def gaps(events: Sequence[Ev]) -> List[Tuple[float, float]]:
    """Idle intervals between the busy intervals of ``events``."""
    u = union((x.start, x.end) for x in events)
    return [(a[1], b[0]) for a, b in zip(u, u[1:]) if b[0] > a[1]]


def matching(events: Sequence[Ev], pattern: str) -> List[Ev]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def total_s(events: Sequence[Ev]) -> float:
    return sum(e.dur for e in events)


def leaves(events: Sequence[Ev]) -> List[Ev]:
    """The operations that contain no other: a control-flow op (the
    ``while`` over a scanned layer stack) spans its body's ops on the
    same line."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt.dur > 0 and nxt.start < e.end
                                   and nxt.end <= e.end)]


def top_ops(events: Sequence[Ev], k: int = 10,
            width: int = 160) -> List[List]:
    """The ``k`` leaf operations that took most device time, each named
    by the first ``width`` characters of its HLO instruction."""
    acc: Dict[str, float] = {}
    for e in leaves(events):
        acc[e.name] = acc.get(e.name, 0.0) + e.dur
    return [[n[:width], s]
            for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_host(idle: Sequence[Tuple[float, float]], host: Sequence[Ev],
                 k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps, each named by the innermost benchmark
    span around its midpoint (``outside engine_step`` where none is)."""
    rank = {n: i for i, n in enumerate(HOST_SPANS)}
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        around = [h for h in host if h.start <= mid <= h.end]
        label = (min(around, key=lambda h: rank[h.name]).name if around
                 else "outside engine_step")
        out.append([label, e - s])
    return out
