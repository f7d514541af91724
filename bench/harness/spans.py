"""The serving engine's own spans in a profiler trace: its host loop.

The engine enters a ``jax.profiler`` annotation at every call site, with
telemetry on or off (``src/repro/serve/telemetry.py``): ``serve/step``
around each ``Engine.step``, ``serve/phase/<phase>`` around its phases
(``budget``, ``admission``, ``prefill``, ``decode``, ``verify``,
``transfer``, ``emit``), ``serve/<entry>`` around each dispatch of a
step program, and ``serve/gc/gen<n>`` around each pass of Python's cycle
collector. They land on the trace's host planes, on the clock of the
device's operations.

The arithmetic here reads them:

  * the host's own work per decode step: from the end of one
    ``serve/phase/transfer`` (the blocking wait for the sampled tokens)
    to the start of the next;
  * the device's idle gaps, each named by the innermost span around it;
  * total and self seconds per span name.

A trace with no ``serve/`` spans (a program older than them) gives
``None`` or empty results, never an error.
"""
from __future__ import annotations

import glob
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace as T

PREFIX = "serve/"
STEP = "serve/step"
TRANSFER = "serve/phase/transfer"
OUTSIDE = "outside serve/step"
NS = 1e-9         # the trace's resolution: ends that meet within it meet


def from_profile(pd) -> List[T.Ev]:
    """The ``serve/`` spans on the host planes of a
    ``jax.profiler.ProfileData`` (or anything of its shape), by start."""
    out = [T.Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda e: (e.start, -e.dur))


def load(trace_dir: str) -> Tuple[T.Trace, List[T.Ev]]:
    """The harness's reduction of a profile directory and the program's
    spans in it, from one read of its newest ``.xplane.pb``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    return T.from_profile(pd), from_profile(pd)


def host_intervals(program: Sequence[T.Ev]) -> List[Tuple[float, float]]:
    """From the end of each ``serve/phase/transfer`` to the start of the
    next: the host's work between two blocking waits for tokens."""
    tr = sorted((e for e in program if e.name == TRANSFER),
                key=lambda e: e.start)
    return [(a.end, b.start) for a, b in zip(tr, tr[1:])]


def host_ms_per_step(program: Sequence[T.Ev]) -> Optional[float]:
    """Median of :func:`host_intervals`, in milliseconds."""
    iv = host_intervals(program)
    if not iv:
        return None
    return 1e3 * statistics.median(e - s for s, e in iv)


def host_share(program: Sequence[T.Ev], window_s: float) -> Optional[float]:
    """:func:`host_intervals` summed, stalls included, as a share (%) of
    the traced window."""
    iv = host_intervals(program)
    if not iv or window_s <= 0:
        return None
    return 100.0 * sum(e - s for s, e in iv) / window_s


def innermost(program: Sequence[T.Ev], t: float) -> str:
    """The name of the shortest ``serve/`` span around time ``t``."""
    around = [e for e in program if e.start <= t <= e.end]
    return min(around, key=lambda e: e.dur).name if around else OUTSIDE


def idle_gaps_by_phase(idle: Sequence[Tuple[float, float]],
                       program: Sequence[T.Ev], k: int = 10) -> List[List]:
    """The ``k`` longest device-idle gaps, each named by the innermost
    ``serve/`` span around its midpoint (``outside serve/step`` where
    none is)."""
    return [[innermost(program, 0.5 * (s + e)), e - s]
            for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:k]]


def host_phases(program: Sequence[T.Ev]) -> Dict[str, Dict[str, float]]:
    """Per span name: how many, total seconds and self seconds (the total
    less the spans nested directly inside), longest total first."""
    acc: Dict[str, Dict[str, float]] = {}
    stack: List[Tuple[T.Ev, Dict[str, float]]] = []
    for e in sorted(program, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start + NS:
            stack.pop()
        row = acc.setdefault(e.name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += e.dur
        row["self_s"] += e.dur
        if stack and e.end <= stack[-1][0].end + NS:
            stack[-1][1]["self_s"] -= e.dur
        stack.append((e, row))
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]["total_s"]))
