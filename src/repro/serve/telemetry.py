"""Serve-side telemetry: metrics registry, lifecycle tracing, JAX hooks.

One dependency-free observability layer for the serving stack, replacing
the ad-hoc ``time.perf_counter()`` calls and per-scheduler ``stats()``
dicts that grew with PRs 1–5. Four pieces:

  * **Metrics registry** (:class:`MetricsRegistry`): named counters,
    gauges, and fixed log-spaced-bucket histograms. ``snapshot()``
    returns one flat JSON-serializable dict (legacy ``Engine.stats()``
    keys preserved verbatim — the engine, scheduler, page pool, and
    prefix cache all *publish* into the registry at collection time, so
    the snapshot is uniform across bucketed/continuous/paged modes);
    ``prometheus()`` renders the standard text exposition format.
  * **Request-lifecycle + step tracing** (:class:`Tracer`,
    :class:`Telemetry`): every request emits spans (queued → admitted →
    prefill-chunk[i] → first-token → decode → retired) on its own
    Chrome-trace thread lane, and every engine ``step()`` emits a phase
    breakdown (admission, chunk prefill, decode dispatch, host
    transfer, and the emit of the sampled tokens). Exported as Chrome
    trace-event JSON (loadable in Perfetto / ``chrome://tracing``) and
    as a JSONL event stream for programmatic analysis. An opt-in
    ``sync`` fence (``block_until_ready`` after device dispatch)
    attributes device time to the phase that launched it instead of
    hiding it in the next host transfer.
  * **JAX-level hooks**: per-entry-point compile tracking — distinct
    dispatched shapes plus real backend-compile seconds via
    ``jax.monitoring`` duration events (the shape-churn recompile
    detector chunked prefill was built to avoid), and optional
    ``jax.profiler`` trace capture for the first N engine steps
    (``profile_dir``).
  * **Profiler spans**, on with telemetry on or off: every call site
    enters a ``jax.profiler`` annotation, so any profiler capture shows
    ``serve/step`` (a step marker carrying ``step_num``), its phases
    ``serve/phase/<phase>``, the dispatches ``serve/<entry>`` and the
    cycle collector's pauses ``serve/gc/gen<n>`` on the device trace's
    clock. With no capture running an annotation costs about a
    microsecond to enter and exit.

When disabled, the engine holds a :data:`NULL_TELEMETRY` recorder that
records nothing — no timestamps taken, no events or histograms stored —
and whose context managers are the profiler annotations alone.

Also here: the shared **interpolating percentile** helper (numpy
"linear" method). The previous hand-rolled index math
(``lats[int(0.95 * len(lats))]``) overshoots p95 for small n and
``lats[n // 2]`` is not the median for even n; every consumer
(``launch/serve.py``, the serve benchmarks) now goes through
:func:`percentile`.
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import json
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation


# ==========================================================================
# Percentiles (shared helper — the single implementation in the repo)
# ==========================================================================
def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolating percentile of ``values`` at quantile ``q``
    in [0, 1] — numpy's default ("linear") method, so
    ``percentile(v, q) == np.percentile(v, 100 * q)`` exactly.

    Unlike the index-truncation shortcut ``v[int(q * len(v))]`` this
    neither overshoots small-n upper percentiles (p95 of 10 samples is
    between the 9th and 10th order statistic, not the maximum) nor
    mis-picks the even-n median (mean of the two middle samples)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of empty sequence")
    pos = q * (len(vals) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


def latency_summary(values: Sequence[float], scale: float = 1.0
                    ) -> Dict[str, float]:
    """p50/p95/p99 + mean/max of ``values`` (× ``scale``, e.g. 1e3 for
    ms) — the common TTFT/ITL reporting shape. Empty input → zeros."""
    vals = [float(v) for v in values]
    if not vals:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "mean": 0.0, "max": 0.0}
    return {"p50": percentile(vals, 0.50) * scale,
            "p95": percentile(vals, 0.95) * scale,
            "p99": percentile(vals, 0.99) * scale,
            "mean": sum(vals) / len(vals) * scale,
            "max": max(vals) * scale}


# ==========================================================================
# Metrics registry
# ==========================================================================
def log_buckets(lo: float = 1e-5, hi: float = 100.0,
                per_decade: int = 4) -> List[float]:
    """Geometric bucket upper bounds: ``per_decade`` boundaries per
    decade from ``lo`` to ``hi`` inclusive. The default (1e-5 s … 100 s)
    spans microsecond host phases to multi-second cold compiles."""
    if lo <= 0 or hi <= lo:
        raise ValueError(f"need 0 < lo < hi, got lo={lo} hi={hi}")
    n = int(round(math.log10(hi / lo) * per_decade))
    bounds = [lo * 10 ** (i / per_decade) for i in range(n + 1)]
    # snap the last boundary onto hi exactly (float round-off)
    bounds[-1] = hi
    return bounds


class Counter:
    """Monotonic counter. ``inc()`` for event-driven use; ``set()`` for
    publish-at-collection-time use (absolute value from an existing
    tally — how the scheduler/pool/prefix legacy counters flow in)."""
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def set(self, v: float) -> None:
        self.value = float(v)

    def snapshot(self):
        return int(self.value) if self.value == int(self.value) \
            else self.value


class Gauge:
    """Point-in-time value (occupancy, pool residency, hit rate)."""
    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def snapshot(self):
        return int(self.value) if self.value == int(self.value) \
            else self.value


class Histogram:
    """Fixed-bucket histogram over log-spaced boundaries.

    ``counts[i]`` tallies observations ``<= bounds[i]``; the final
    slot is the +Inf overflow. Quantiles are estimated by geometric
    interpolation within the containing bucket (log-spaced buckets →
    log-linear interpolation), clamped to the observed min/max so
    single-bucket distributions don't report a bucket edge."""
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Optional[Sequence[float]] = None):
        self.name = name
        self.help = help
        self.bounds = list(buckets) if buckets is not None else log_buckets()
        if sorted(self.bounds) != self.bounds or len(set(self.bounds)) \
                != len(self.bounds):
            raise ValueError(f"{name}: bucket bounds must be strictly "
                             f"increasing")
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.sum += v
        self.count += 1
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    def quantile(self, q: float) -> Optional[float]:
        """Bucket-interpolated quantile estimate (None when empty)."""
        if self.count == 0:
            return None
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = self.bounds[i - 1] if i > 0 else min(self.min,
                                                          self.bounds[0])
                hi = self.bounds[i] if i < len(self.bounds) else self.max
                lo = max(lo, self.min)
                hi = min(hi, self.max)
                if lo <= 0 or hi <= lo:
                    return hi
                frac = (target - cum) / c
                return lo * (hi / lo) ** frac
            cum += c
        return self.max

    def reset(self) -> None:
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = math.inf
        self.max = -math.inf

    def snapshot(self) -> Dict[str, Optional[float]]:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "min": None, "max": None,
                    "p50": None, "p95": None, "p99": None}
        return {"count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max,
                "p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}


def _fmt(v: float) -> str:
    return f"{v:.9g}"


class MetricsRegistry:
    """Name → metric map with typed get-or-create accessors.

    ``snapshot()`` flattens to one JSON dict (histograms nest their
    summary under their name); ``prometheus()`` renders the text
    exposition format. Re-requesting a name with a different metric
    type is a programming error and raises."""

    def __init__(self):
        self._metrics: Dict[str, Any] = {}

    def _get(self, cls, name: str, help: str, **kw):
        m = self._metrics.get(name)
        if m is None:
            m = cls(name, help, **kw)
            self._metrics[name] = m
        elif not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{m.kind}, requested {cls.kind}")
        return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def __contains__(self, name: str) -> bool:
        return name in self._metrics

    def snapshot(self) -> Dict[str, Any]:
        return {name: m.snapshot() for name, m in self._metrics.items()}

    def prometheus(self) -> str:
        """Prometheus text exposition (histograms in the standard
        cumulative ``_bucket{le=...}`` / ``_sum`` / ``_count`` form)."""
        lines: List[str] = []
        for name, m in self._metrics.items():
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {m.kind}")
            if isinstance(m, Histogram):
                cum = 0
                for bound, c in zip(m.bounds, m.counts):
                    cum += c
                    lines.append(f'{name}_bucket{{le="{_fmt(bound)}"}} {cum}')
                lines.append(f'{name}_bucket{{le="+Inf"}} {m.count}')
                lines.append(f"{name}_sum {_fmt(m.sum)}")
                lines.append(f"{name}_count {m.count}")
            else:
                lines.append(f"{name} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"

    def reset_histograms(self) -> None:
        """Clear histogram samples (counters/gauges are publish-time
        absolutes and need no reset) — a fresh ``generate()`` run must
        not inherit the warmup dummy's latencies."""
        for m in self._metrics.values():
            if isinstance(m, Histogram):
                m.reset()


# ==========================================================================
# Chrome trace-event tracer
# ==========================================================================
PID_REQUESTS = 1      # request-lifecycle lanes (tid = request uid)
PID_ENGINE = 2        # engine step/phase timeline (tid 0)


class Tracer:
    """Chrome trace-event buffer (JSON array format).

    Events carry microsecond timestamps relative to the tracer's birth
    (one shared ``time.perf_counter`` origin, so engine-side
    ``perf_counter`` readings convert via :meth:`us`). ``chrome()``
    wraps the buffer for Perfetto / ``chrome://tracing``;
    ``write_jsonl`` streams the same records one-per-line for
    programmatic analysis."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.events: List[Dict[str, Any]] = []
        self._metadata()

    def _metadata(self) -> None:
        for pid, name in ((PID_REQUESTS, "requests"), (PID_ENGINE, "engine")):
            self.events.append({"ph": "M", "pid": pid, "tid": 0, "ts": 0,
                                "name": "process_name",
                                "args": {"name": name}})

    # ------------------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    def us(self, t_perf: float) -> float:
        """Convert an absolute ``time.perf_counter()`` reading."""
        return (t_perf - self.t0) * 1e6

    def complete(self, name: str, ts_us: float, dur_us: float, pid: int,
                 tid: int, args: Optional[Dict] = None) -> None:
        ev = {"ph": "X", "name": name, "ts": round(ts_us, 3),
              "dur": round(max(dur_us, 0.0), 3), "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, ts_us: float, pid: int, tid: int,
                args: Optional[Dict] = None) -> None:
        ev = {"ph": "i", "name": name, "ts": round(ts_us, 3), "pid": pid,
              "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # ------------------------------------------------------------------
    def chrome(self) -> Dict[str, Any]:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def write_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome(), f)
            f.write("\n")
        return path

    def write_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")
        return path

    def reset(self) -> None:
        """Drop buffered events; the time origin is kept so timestamps
        stay monotonic across engine runs."""
        self.events = []
        self._metadata()


# ==========================================================================
# JAX compile-duration listener (module-level: jax.monitoring listeners
# cannot be unregistered individually, so one forwarding hook is
# installed lazily and routes to whichever Telemetry is mid-dispatch)
# ==========================================================================
_listener_state = {"installed": False}
_current_telemetry: Optional["Telemetry"] = None


def _install_compile_listener() -> None:
    if _listener_state["installed"]:
        return
    from jax import monitoring

    def _on_duration(event: str, duration: float, **kw) -> None:
        tel = _current_telemetry
        if tel is not None and "backend_compile" in event:
            tel._note_compile_seconds(duration)

    monitoring.register_event_duration_secs_listener(_on_duration)
    _listener_state["installed"] = True


# ==========================================================================
# Profiler spans: the names every engine call site enters, recorder or not
# ==========================================================================
STEP_PHASES = ("budget", "admission", "prefill", "decode", "verify",
               "transfer", "emit")
STEP_SPAN = "serve/step"
PHASE_SPANS = {p: f"serve/phase/{p}" for p in STEP_PHASES}


class StepSpan:
    """``serve/step`` around one ``Engine.step``: a profiler step
    marker carrying ``step_num``. The engine sets ``decoding`` (lanes
    decoded this step) before the span closes."""

    __slots__ = ("_ann", "decoding")

    def __init__(self, num: int):
        self._ann = StepTraceAnnotation(STEP_SPAN, step_num=num)
        self.decoding = 0

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False


class _GcPauses:
    """``gc.callbacks`` hook: each cycle-collector pass becomes a
    ``serve/gc/gen<n>`` profiler span and is counted, process-wide."""

    def __init__(self):
        self.collections = 0
        self.gen2 = 0
        self.pause_s = 0.0
        self._span = None
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._span = TraceAnnotation(f"serve/gc/gen{info['generation']}")
            self._span.__enter__()
            self._t0 = time.perf_counter()
        elif self._span is not None:
            self.pause_s += time.perf_counter() - self._t0
            self._span.__exit__(None, None, None)
            self._span = None
            self.collections += 1
            self.gen2 += info["generation"] == 2

    def totals(self) -> Tuple[int, int, float]:
        return self.collections, self.gen2, self.pause_s


_GC = _GcPauses()


def install_gc_hook() -> _GcPauses:
    """Install the collector hook once per process (every ``Engine``
    calls this; a second call is a no-op) and return it."""
    if _GC not in gc.callbacks:
        gc.callbacks.append(_GC)
    return _GC


def remove_gc_hook() -> None:
    """Take the collector hook out of ``gc.callbacks`` (its totals
    stay); the next ``Engine`` puts it back."""
    if _GC in gc.callbacks:
        gc.callbacks.remove(_GC)


# ==========================================================================
# Telemetry facade
# ==========================================================================
class Telemetry:
    """Live recorder the engine drives; owns the tracer and publishes
    request/step histograms plus compile stats into the (shared)
    registry. Construct with ``sync=True`` to fence device dispatches
    (``block_until_ready``) so device time lands in the phase that
    launched it. ``profile_dir`` arms ``jax.profiler`` capture for the
    first ``profile_steps`` engine steps."""

    enabled = True

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 sync: bool = False, profile_dir: Optional[str] = None,
                 profile_steps: int = 20):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = Tracer()
        self.sync = sync
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        self._profiling = False
        self._profile_done = False
        self._step_idx = 0
        self._requests: Dict[int, Dict[str, float]] = {}
        # entry point → dispatch/compile accounting
        self.compiles: Dict[str, Dict[str, Any]] = {}
        self._entry_name: Optional[str] = None
        _install_compile_listener()
        reg = self.registry
        self._h_step = reg.histogram("step_seconds", "engine step wall time")
        self._h_phase = {p: reg.histogram(f"step_{p}_seconds",
                                          f"step {p} phase wall time")
                         for p in STEP_PHASES}
        self._h_ttft = reg.histogram("ttft_seconds",
                                     "submit to first token")
        self._h_latency = reg.histogram("request_latency_seconds",
                                        "submit to retirement")
        self._h_itl = reg.histogram("itl_seconds",
                                    "inter-token latency (decode span / "
                                    "(tokens - 1))")
        self._h_chunk = reg.histogram("prefill_chunk_seconds",
                                      "one chunked-prefill dispatch")

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def request_queued(self, uid: int) -> None:
        self._requests[uid] = {"queued": self.tracer.now_us()}

    def request_admitted(self, uid: int) -> None:
        now = self.tracer.now_us()
        r = self._requests.setdefault(uid, {})
        q = r.get("queued", now)
        r["admitted"] = now
        self.tracer.complete("queued", q, now - q, PID_REQUESTS, uid)

    def request_prefill(self, uid: int, index: int, t0: float,
                        t1: float) -> None:
        """One prefill dispatch for ``uid`` (chunk ``index``; unpaged
        prefill-on-admit is chunk 0). ``t0``/``t1`` are perf_counter."""
        self._h_chunk.observe(t1 - t0)
        self.tracer.complete(f"prefill_chunk[{index}]", self.tracer.us(t0),
                             (t1 - t0) * 1e6, PID_REQUESTS, uid)

    def request_first_token(self, uid: int) -> None:
        now = self.tracer.now_us()
        r = self._requests.setdefault(uid, {})
        a = r.get("admitted", now)
        r["first_token"] = now
        self.tracer.complete("prefill", a, now - a, PID_REQUESTS, uid)
        self.tracer.instant("first_token", now, PID_REQUESTS, uid)

    def request_retired(self, uid: int, n_tokens: int,
                        ttft_s: Optional[float],
                        latency_s: Optional[float],
                        decode_s: Optional[float]) -> None:
        now = self.tracer.now_us()
        r = self._requests.pop(uid, {})
        ft = r.get("first_token")
        if ft is not None:
            self.tracer.complete("decode", ft, now - ft, PID_REQUESTS, uid,
                                 args={"tokens": n_tokens})
        elif "admitted" in r:
            # retired without ever sampling (max_new_tokens=0): close the
            # prefill span so the lane still covers queued → retired
            self.tracer.complete("prefill", r["admitted"],
                                 now - r["admitted"], PID_REQUESTS, uid)
        self.tracer.instant("retired", now, PID_REQUESTS, uid,
                            args={"tokens": n_tokens})
        if ttft_s is not None:
            self._h_ttft.observe(ttft_s)
        if latency_s is not None:
            self._h_latency.observe(latency_s)
        if decode_s is not None and n_tokens > 1:
            self._h_itl.observe(decode_s / (n_tokens - 1))

    # ------------------------------------------------------------------
    # Engine step phases
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def step(self, num: int):
        """One engine step: the ``serve/step`` span, the step histogram
        and the trace's step event. A capture armed by ``profile_dir``
        starts before the span opens and stops after it closes, so the
        captured steps are whole."""
        if self.profile_dir and not self._profile_done and not self._profiling:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
        t0 = time.perf_counter()
        span = StepSpan(num)
        try:
            with span:
                yield span
        finally:
            t1 = time.perf_counter()
            self._h_step.observe(t1 - t0)
            self.tracer.complete("step", self.tracer.us(t0),
                                 (t1 - t0) * 1e6, PID_ENGINE, 0,
                                 args={"step": self._step_idx,
                                       "decoding": span.decoding})
            self._step_idx += 1
            if self._profiling and self._step_idx >= self.profile_steps:
                self.stop_profiler()

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(PHASE_SPANS[name]):
                yield
        finally:
            t1 = time.perf_counter()
            self._h_phase[name].observe(t1 - t0)
            self.tracer.complete(name, self.tracer.us(t0), (t1 - t0) * 1e6,
                                 PID_ENGINE, 0)

    # ------------------------------------------------------------------
    # JAX hooks: compile tracking + profiler annotations
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def entry(self, name: str, shape_key: Tuple):
        """Wrap one jitted-entry-point dispatch. Tracks distinct
        ``shape_key`` signatures per entry (a growing set = the shape
        churn chunked prefill exists to avoid), attributes
        ``jax.monitoring`` backend-compile seconds to this entry while
        the dispatch is live, and labels the region ``serve/<name>``
        for the JAX profiler timeline."""
        global _current_telemetry
        info = self.compiles.setdefault(
            name, {"shapes": set(), "compiles": 0, "calls": 0,
                   "compile_seconds": 0.0})
        info["calls"] += 1
        first = shape_key not in info["shapes"]
        prev, _current_telemetry = _current_telemetry, self
        self._entry_name = name
        try:
            with TraceAnnotation(f"serve/{name}"):
                yield
        finally:
            _current_telemetry = prev
            if first:
                info["shapes"].add(shape_key)
                info["compiles"] += 1
                self.tracer.instant(f"compile:{name}", self.tracer.now_us(),
                                    PID_ENGINE, 0,
                                    args={"shape": str(shape_key)})

    def _note_compile_seconds(self, seconds: float) -> None:
        info = self.compiles.get(getattr(self, "_entry_name", None))
        if info is not None:
            info["compile_seconds"] += seconds

    def stop_profiler(self) -> None:
        if self._profiling:
            import jax
            self._profiling = False
            self._profile_done = True
            jax.profiler.stop_trace()

    # ------------------------------------------------------------------
    def publish(self) -> None:
        """Push compile accounting into the registry (histograms are
        registry-resident already)."""
        reg = self.registry
        for name, info in self.compiles.items():
            reg.gauge(f"compiled_shapes_{name}",
                      f"distinct dispatched shapes for {name}"
                      ).set(len(info["shapes"]))
            reg.counter(f"dispatches_{name}",
                        f"total {name} dispatches").set(info["calls"])
            reg.gauge(f"compile_seconds_{name}",
                      f"jax backend-compile seconds attributed to {name}"
                      ).set(round(info["compile_seconds"], 6))

    def reset_run(self) -> None:
        """Start a fresh measured run: drop trace events, open request
        spans, and histogram samples. Compile accounting survives — it
        describes the engine session, not one run."""
        self.tracer.reset()
        self._requests.clear()
        self._step_idx = 0
        self.registry.reset_histograms()

    def close(self) -> None:
        self.stop_profiler()


# ==========================================================================
# Disabled recorder: every engine call site dispatches through one of
# these — no timestamps, no events, no histograms; its context managers
# are the profiler annotations alone. Shared singleton.
# ==========================================================================
class NullTelemetry:
    """Recording-free recorder; ``Engine`` holds this when telemetry is
    off. Steps, phases and dispatches still enter their profiler spans."""

    enabled = False
    sync = False
    registry = None
    tracer = None

    def request_queued(self, uid):
        pass

    def request_admitted(self, uid):
        pass

    def request_prefill(self, uid, index, t0, t1):
        pass

    def request_first_token(self, uid):
        pass

    def request_retired(self, uid, n_tokens, ttft_s, latency_s, decode_s):
        pass

    def step(self, num):
        return StepSpan(num)

    def phase(self, name):
        return TraceAnnotation(PHASE_SPANS[name])

    def entry(self, name, shape_key):
        return TraceAnnotation(f"serve/{name}")

    def publish(self):
        pass

    def reset_run(self):
        pass

    def stop_profiler(self):
        pass

    def close(self):
        pass


NULL_TELEMETRY = NullTelemetry()
