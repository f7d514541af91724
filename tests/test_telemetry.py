"""Serve telemetry: percentiles, registry, tracing, and engine wiring.

Acceptance-criteria coverage for the observability PR: the shared
interpolating percentile against numpy oracles (including the exact
small-n bias the old index shortcut had), histogram bucketing against
``np.searchsorted``, registry snapshot/prometheus form, Chrome-trace
well-formedness, telemetry-on vs -off token parity on the paged int4
fused engine, the zero-budget ``Result`` timing regression, compile
tracking, and the checked-in metrics schema via the CI validator.
"""
import gc
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_lm
from repro.serve import (Engine, MetricsRegistry, Request, ServeConfig,
                         latency_summary, percentile)
from repro.serve.telemetry import (Histogram, Telemetry, install_gc_hook,
                                   log_buckets, remove_gc_hook)

REPO = Path(__file__).resolve().parents[1]
GC_KEYS = ("gc_collections", "gc_collections_gen2", "gc_pause_seconds")


# ---------------------------------------------------------------------------
# Percentile helper vs numpy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 3, 10, 17, 100])
@pytest.mark.parametrize("q", [0.0, 0.25, 0.50, 0.90, 0.95, 0.99, 1.0])
def test_percentile_matches_numpy(n, q):
    rng = np.random.default_rng(n * 1000 + int(q * 100))
    vals = rng.exponential(size=n).tolist()
    assert percentile(vals, q) == pytest.approx(
        float(np.percentile(vals, q * 100)), rel=1e-12)


def test_percentile_fixes_the_old_index_bias():
    """The replaced shortcuts: ``v[int(.95*n)]`` returned the maximum
    of 10 samples for p95, and ``v[n//2]`` is not the even-n median."""
    v = list(range(1, 11))                     # 1..10
    assert v[min(len(v) - 1, int(0.95 * len(v)))] == 10   # old: the max
    assert percentile(v, 0.95) == pytest.approx(9.55)     # interpolated
    assert v[len(v) // 2] == 6                 # old "median" of 10
    assert percentile(v, 0.50) == pytest.approx(5.5)
    assert percentile([1, 2, 3, 4], 0.50) == pytest.approx(2.5)


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        percentile([], 0.5)
    with pytest.raises(ValueError):
        percentile([1.0], 1.5)


def test_latency_summary():
    s = latency_summary([0.1, 0.2, 0.3], scale=1e3)
    assert s["p50"] == pytest.approx(200.0)
    assert s["max"] == pytest.approx(300.0)
    assert latency_summary([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                                   "mean": 0.0, "max": 0.0}


# ---------------------------------------------------------------------------
# Histogram vs numpy bucketing
# ---------------------------------------------------------------------------
def test_log_buckets_shape():
    b = log_buckets(1e-5, 100.0, per_decade=4)
    assert b[0] == 1e-5 and b[-1] == 100.0
    assert b == sorted(b)
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.25, rel=1e-6) for r in ratios)


def test_histogram_counts_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6, sigma=2, size=500)
    h = Histogram("h")
    for v in samples:
        h.observe(float(v))
    # counts[i] tallies v <= bounds[i] (bisect_left), overflow last
    idx = np.searchsorted(h.bounds, samples, side="left")
    expect = np.bincount(idx, minlength=len(h.bounds) + 1)
    assert h.counts == expect.tolist()
    assert h.count == 500
    assert h.sum == pytest.approx(float(samples.sum()))
    assert h.min == pytest.approx(float(samples.min()))
    assert h.max == pytest.approx(float(samples.max()))


def test_histogram_quantiles_bracket_numpy():
    rng = np.random.default_rng(1)
    samples = rng.lognormal(mean=-3, sigma=1, size=2000)
    h = Histogram("h")
    for v in samples:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        exact = float(np.quantile(samples, q))
        # bucket-resolution estimate: within one geometric bucket width
        assert est / exact == pytest.approx(1.0, rel=10 ** 0.25 - 1)
        assert h.min <= est <= h.max
    empty = Histogram("e")
    assert empty.quantile(0.5) is None
    assert empty.snapshot()["p50"] is None and empty.snapshot()["count"] == 0


def test_single_observation_quantile_is_the_observation():
    h = Histogram("h")
    h.observe(0.0123)
    for q in (0.5, 0.95, 0.99):
        assert h.quantile(q) == pytest.approx(0.0123)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_type_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_registry_snapshot_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("reqs", "requests").inc(3)
    reg.gauge("occ", "occupancy").set(0.5)
    h = reg.histogram("lat", "latency")
    for v in (0.001, 0.01, 0.01, 4.2):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["reqs"] == 3 and isinstance(snap["reqs"], int)
    assert snap["occ"] == 0.5
    assert snap["lat"]["count"] == 4
    assert json.loads(json.dumps(snap)) == snap       # JSON-serializable
    text = reg.prometheus()
    assert "# TYPE reqs counter" in text
    assert "# TYPE occ gauge" in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text
    # cumulative bucket counts must be non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_bucket")]
    assert cums == sorted(cums)


# ---------------------------------------------------------------------------
# Engine wiring: parity, traces, compile tracking, schema
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("phi3-mini-3.8b").reduced()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _reqs(cfg, n, seed=0, budget=None):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=5 + (i % 3))
                    .astype(np.int32),
                    max_new_tokens=budget)
            for i in range(n)]


def _engine(cfg, params, **kw):
    defaults = dict(max_len=64, decode_batch=2, max_new_tokens=5,
                    prefill_len=16, scheduler="continuous")
    defaults.update(kw)
    return Engine(params, cfg, ServeConfig(**defaults))


@pytest.fixture(scope="module")
def paged_runs(tiny):
    """Paged int4 fused engine run twice: telemetry off and fully on."""
    cfg, params = tiny
    base = dict(kv_dtype="int4", fused="on", paged=True, page_size=8)
    res_off = _engine(cfg, params, **base).generate(_reqs(cfg, 5))
    eng_on = _engine(cfg, params, telemetry=True, trace_sync=True, **base)
    res_on = eng_on.generate(_reqs(cfg, 5))
    return eng_on, res_on, res_off


def test_telemetry_is_behaviorally_invisible(paged_runs):
    _, res_on, res_off = paged_runs
    for a, b in zip(res_off, res_on):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_unified_snapshot_preserves_legacy_keys(tiny, paged_runs):
    """Telemetry must only *add* series: every key the disabled engine
    reports appears with the identical value in the enabled snapshot
    (the collector counters are process-wide, so only their presence
    is compared)."""
    cfg, params = tiny
    base = dict(kv_dtype="int4", fused="on", paged=True, page_size=8)
    st_off = _engine(cfg, params, **base)
    st_off.generate(_reqs(cfg, 5))
    st_off = st_off.stats()
    st_on = paged_runs[0].stats()
    for key, val in st_off.items():
        if key in GC_KEYS:
            assert key in st_on
            continue
        assert st_on[key] == val, f"legacy key {key} drifted"
    for key in ("step_seconds", "ttft_seconds", "itl_seconds",
                "prefill_chunk_seconds", "request_latency_seconds"):
        assert key not in st_off           # histograms are telemetry-only
        assert st_on[key]["count"] > 0, f"{key} never observed"
    for ph in ("admission", "prefill", "decode", "transfer", "emit"):
        assert st_on[f"step_{ph}_seconds"]["count"] > 0


def test_bucketed_stats_emit_common_keys(tiny):
    """Satellite: the bucketed scheduler reports the same admission /
    retirement counters as continuous, not just occupancy."""
    cfg, params = tiny
    eng = _engine(cfg, params, scheduler="bucketed", decode_batch=4)
    res = eng.generate(_reqs(cfg, 5))
    st = eng.stats()
    assert st["admitted"] == st["retired"] == len(res) == 5
    assert st["eos_retired"] >= 0
    assert st["decode_slot_steps"] > 0


def test_compile_tracking(paged_runs):
    eng = paged_runs[0]
    st = eng.stats()
    # one decode shape (the whole point of lockstep decode), one chunk
    # shape per width of the engine's chunk ladder
    assert st["compiled_shapes_decode"] == 1
    assert st["compiled_shapes_prefill_chunk"] == len(eng._chunk_widths)
    assert st["dispatches_decode"] > st["compiled_shapes_decode"]
    assert st["compile_seconds_decode"] >= 0


def test_trace_well_formed(paged_runs, tmp_path):
    eng, res_on, _ = paged_runs
    path = tmp_path / "trace.json"
    jsonl = tmp_path / "trace.jsonl"
    eng.write_trace(str(path), jsonl_path=str(jsonl))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events and doc["displayTimeUnit"] == "ms"
    for ev in events:
        assert set(ev) >= {"ph", "name", "pid", "tid", "ts"}
        assert ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    names = {e["name"] for e in events}
    assert {"queued", "prefill", "first_token", "decode", "retired",
            "step", "admission", "transfer"} <= names
    # every request got its own lifecycle lane (pid 1, tid = uid)
    uids = {e["tid"] for e in events
            if e["pid"] == 1 and e["name"] == "retired"}
    assert uids == {r.uid for r in res_on}
    # the decode span starts at/after first_token on each lane
    for uid in uids:
        ft = [e for e in events if e["pid"] == 1 and e["tid"] == uid
              and e["name"] == "first_token"]
        dec = [e for e in events if e["pid"] == 1 and e["tid"] == uid
               and e["name"] == "decode"]
        assert len(ft) == 1 and len(dec) == 1
        assert dec[0]["ts"] >= ft[0]["ts"] - 1e-3
    lines = jsonl.read_text().strip().splitlines()
    assert [json.loads(ln) for ln in lines] == events


def test_write_trace_requires_telemetry(tiny, tmp_path):
    cfg, params = tiny
    eng = _engine(cfg, params)
    with pytest.raises(RuntimeError):
        eng.write_trace(str(tmp_path / "t.json"))


def test_zero_budget_result_timing(tiny):
    """Regression: ``max_new_tokens=0`` retires without decoding —
    ``decode_s``/``ttft_s`` must be None (not a fake 0.0), latency
    still measured, zero tokens emitted."""
    cfg, params = tiny
    res = _engine(cfg, params).generate(_reqs(cfg, 2, budget=0))
    for r in res:
        assert len(r.tokens) == 0
        assert r.decode_s is None
        assert r.ttft_s is None
        assert r.latency_s is not None and r.latency_s > 0


def test_result_timings_populated_when_decoding(tiny):
    cfg, params = tiny
    res = _engine(cfg, params).generate(_reqs(cfg, 2))
    for r in res:
        assert r.ttft_s is not None and r.ttft_s > 0
        assert r.latency_s >= r.ttft_s
        assert r.decode_s is not None and r.decode_s >= 0


def test_metrics_snapshot_matches_checked_in_schema(paged_runs, tmp_path):
    """The CI smoke's contract: a paged telemetry snapshot validates
    against tools/metrics_schema.json via the repo validator."""
    path = tmp_path / "metrics.json"
    path.write_text(json.dumps(paged_runs[0].stats()))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "validate_metrics.py"),
         str(path)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    # and the validator actually rejects drift
    bad = dict(paged_runs[0].stats())
    del bad["occupancy"]
    path.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "validate_metrics.py"),
         str(path)], capture_output=True, text=True)
    assert proc.returncode == 1
    assert "occupancy" in proc.stderr


def test_null_telemetry_interface_is_complete():
    """Every public method/attr the engine calls on a live Telemetry
    must exist on the null recorder (and vice versa stay no-op)."""
    from repro.serve.telemetry import NULL_TELEMETRY
    live = [n for n in dir(Telemetry) if not n.startswith("_")
            and callable(getattr(Telemetry, n))]
    for name in live:
        assert hasattr(NULL_TELEMETRY, name), f"NullTelemetry lacks {name}"
    assert NULL_TELEMETRY.enabled is False
    with NULL_TELEMETRY.phase("decode"):
        pass
    with NULL_TELEMETRY.entry("decode", (1, 2)):
        pass
    with NULL_TELEMETRY.step(7) as span:
        span.decoding = 2


# ---------------------------------------------------------------------------
# Profiler spans, collector pauses and the decode context count
# ---------------------------------------------------------------------------
def _host_spans(trace_dir):
    """``serve/`` spans of a profile directory, as (name, start, end)."""
    from jax.profiler import ProfileData
    pb = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in pd.planes if p.name.startswith("/host:")
                   for ln in p.lines for e in ln.events
                   if e.name.startswith("serve/")), key=lambda e: e[1])


@pytest.mark.parametrize("telemetry", [False, True])
def test_forced_collection_is_a_span_and_counted(tiny, tmp_path, telemetry):
    """A full collection inside a step's emit phase shows as a
    ``serve/gc/gen2`` span nested in ``serve/phase/emit`` and moves the
    always-live collector counters, with telemetry off or on."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, telemetry=telemetry)
    guard = eng._host_guard

    def collecting_guard(toks, decoding):
        gc.collect()
        guard(toks, decoding)

    eng._host_guard = collecting_guard
    try:
        for r in _reqs(cfg, 2):
            eng.submit(r)
        st0 = eng.stats()
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.drain()
        finally:
            jax.profiler.stop_trace()
        st1 = eng.stats()
        assert st1["gc_collections_gen2"] > st0["gc_collections_gen2"]
        assert st1["gc_collections"] >= st1["gc_collections_gen2"]
        assert st1["gc_pause_seconds"] > st0["gc_pause_seconds"]
        spans = _host_spans(tmp_path)
        emits = [s for s in spans if s[0] == "serve/phase/emit"]
        gen2 = [s for s in spans if s[0] == "serve/gc/gen2"]
        assert emits and len(gen2) >= len(emits)
        for _, a, b in emits:
            assert any(a <= s and e <= b for _, s, e in gen2)
        assert "gc_collections_gen2" in eng.prometheus()
    finally:
        remove_gc_hook()


def test_gc_hook_is_installed_once_and_removable(tiny):
    cfg, params = tiny
    try:
        _engine(cfg, params)
        eng = _engine(cfg, params)
        hook = install_gc_hook()
        assert sum(cb is hook for cb in gc.callbacks) == 1
        remove_gc_hook()
        assert hook not in gc.callbacks
        n = eng.stats()["gc_collections"]
        gc.collect()
        assert eng.stats()["gc_collections"] == n
        eng.reset_stats()
        assert eng.stats()["gc_collections"] == 0
    finally:
        remove_gc_hook()


def test_disabled_recorder_only_annotates(tiny):
    """Telemetry off: the call sites enter profiler annotations and
    record nothing — no events, no step or phase histograms."""
    from jax.profiler import TraceAnnotation
    from repro.serve.telemetry import NULL_TELEMETRY, STEP_PHASES
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8)
    assert eng.tel is NULL_TELEMETRY and NULL_TELEMETRY.tracer is None
    for ph in STEP_PHASES:
        assert isinstance(NULL_TELEMETRY.phase(ph), TraceAnnotation)
    assert isinstance(NULL_TELEMETRY.entry("decode", (2, 1)),
                      TraceAnnotation)
    eng.generate(_reqs(cfg, 3))
    st = eng.stats()
    assert not any(k.startswith("step_") for k in st)


@pytest.mark.parametrize("paged", [False, True])
def test_decode_context_tokens(tiny, paged):
    """One request of prompt P and budget N decodes N - 1 steps, the
    j-th over P + j tokens: (N - 1) P + N (N - 1) / 2 in all."""
    cfg, params = tiny
    kw = dict(paged=True, page_size=8) if paged else {}
    eng = _engine(cfg, params, **kw)
    req = _reqs(cfg, 1, budget=5)[0]
    plen, n = len(req.prompt), 5
    res = eng.generate([req])
    assert len(res[0].tokens) == n
    st = eng.stats()
    assert st["decode_steps"] == n - 1
    assert st["decode_context_tokens"] == (n - 1) * plen + n * (n - 1) // 2
