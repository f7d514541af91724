"""Token-budget step scheduler, per-request sampling, abort lifecycle."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_lm
from repro.serve import Engine, Request, SamplingParams, ServeConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("phi3-mini-3.8b").reduced()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    defaults = dict(max_len=64, decode_batch=3, max_new_tokens=6,
                    prefill_len=16, scheduler="continuous")
    defaults.update(kw)
    return Engine(params, cfg, ServeConfig(**defaults))


def _reqs(cfg, n, base_len=5, budget=None, params=None):
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=base_len + (i % 3))
                    .astype(np.int32),
                    max_new_tokens=budget[i] if budget else None,
                    params=params[i] if params else None)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Budget invariants
# ---------------------------------------------------------------------------
def _drain_counting(eng):
    """Step the engine to empty, asserting the per-step charge invariant
    from the stats deltas: prefill dispatches at the widths dispatched
    (paged: the summed chunk widths of ``prefill_chunk_tokens_dispatched``;
    unpaged: one compiled prefill per admission) + decode lanes never
    exceed max_step_tokens."""
    limit = eng.sc.max_step_tokens
    unit = eng._step_unit

    def dispatched():
        return eng.stats()["prefill_chunk_tokens_dispatched"] \
            if eng.sc.paged else eng.sched.stats.admitted * unit

    results = []
    while eng.sched.has_work:
        dec0, pre0 = eng.sched.stats.decode_slot_steps, dispatched()
        results.extend(eng.step())
        spent = dispatched() - pre0
        spent += eng.sched.stats.decode_slot_steps - dec0
        assert spent <= limit, f"step spent {spent} > budget {limit}"
    results.sort(key=lambda r: r.uid)
    return results


def test_budget_never_exceeded_paged(tiny):
    """Under a burst of multi-chunk prompts the per-step work stays
    within max_step_tokens, and the deferral counters tick."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=160,
                  prefill_len=16, decode_batch=4, prefix_cache=False,
                  max_new_tokens=8, max_step_tokens=16 + 4)
    rng = np.random.default_rng(1)
    for i in range(6):   # all multi-chunk prompts, arriving at once
        eng.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab, size=40 + i).astype(np.int32)))
    res = _drain_counting(eng)
    assert [r.uid for r in res] == list(range(6))
    assert all(len(r.tokens) == 8 for r in res)
    st = eng.stats()
    assert st["budget_deferred_admissions"] > 0 \
        or st["budget_capped_chunks"] > 0


def test_budget_never_exceeded_unpaged(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_step_tokens=16 + 1, decode_batch=3)
    for r in _reqs(cfg, 6):
        eng.submit(r)
    res = _drain_counting(eng)
    assert [r.uid for r in res] == list(range(6))
    st = eng.stats()
    assert st["budget_deferred_admissions"] > 0


def test_budget_does_not_change_tokens(tiny):
    """Scheduling-independent sampling: the budget defers work but must
    never change any request's output."""
    cfg, params = tiny
    sp = [None, SamplingParams(temperature=0.8, seed=7), None,
          SamplingParams(temperature=1.2, top_k=9), None, None]
    outs = []
    for mst in (None, 17):
        eng = _engine(cfg, params, paged=True, page_size=8, max_len=160,
                      prefill_len=16, decode_batch=4, max_new_tokens=8,
                      max_step_tokens=mst)
        outs.append(eng.generate(_reqs(cfg, 6, base_len=30, params=sp)))
    for a, b in zip(*outs):
        assert a.uid == b.uid
        assert a.tokens.tolist() == b.tokens.tolist()
        assert a.finish_reason == b.finish_reason


def test_budget_validation():
    cfg = get_config("phi3-mini-3.8b").reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="max_step_tokens"):
        _engine(cfg, params, max_step_tokens=8, prefill_len=16)
    with pytest.raises(ValueError, match="continuous"):
        _engine(cfg, params, scheduler="bucketed", max_step_tokens=64)


# ---------------------------------------------------------------------------
# Page quota + watermark eviction
# ---------------------------------------------------------------------------
def test_page_quota_clamps_budget(tiny):
    """max_pages_per_request caps prompt+generation pages: generation
    stops when the quota's last page fills."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=160,
                  prefill_len=16, max_new_tokens=64,
                  max_pages_per_request=2)
    rng = np.random.default_rng(0)
    res = eng.generate([Request(uid=0, prompt=rng.integers(
        0, cfg.vocab, size=10).astype(np.int32))])
    # 2 pages * 8 slots - 10 prompt tokens = 6 generated tokens
    assert len(res[0].tokens) == 6
    assert res[0].finish_reason == "length"

    with pytest.raises(ValueError, match="max_pages_per_request"):
        eng.submit(Request(uid=1, prompt=rng.integers(
            0, cfg.vocab, size=16).astype(np.int32)))


def test_quota_watermark_need_paged(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        _engine(cfg, params, max_pages_per_request=2)
    with pytest.raises(ValueError, match="paged"):
        _engine(cfg, params, free_watermark=0.5)


def test_watermark_evicts_cold_pages(tiny):
    """free_watermark drains cold prefix pages ahead of demand."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=64,
                  prefill_len=16, max_new_tokens=4, free_watermark=0.9)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=20).astype(np.int32)
    eng.generate([Request(uid=0, prompt=prompt)])
    # the retired request's full prompt blocks sit cold in the tree;
    # the next step's watermark pass must reclaim them
    assert eng.pool.n_cold > 0
    eng.submit(Request(uid=1, prompt=prompt[:5].copy()))
    eng.drain()
    st = eng.stats()
    assert st["watermark_evictions"] > 0
    assert eng.pool.n_cold == 0 or eng.pool.n_free >= int(
        0.9 * eng.pool.n_pages)


# ---------------------------------------------------------------------------
# Abort lifecycle (incl. the mid-prefill refcount regression)
# ---------------------------------------------------------------------------
def test_abort_queued_and_decoding(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_new_tokens=20)
    for r in _reqs(cfg, 4):
        eng.submit(r)
    eng.step()           # admits up to 3, request 3 still queued
    res_q = eng.abort(3)
    assert res_q.finish_reason == "abort" and len(res_q.tokens) == 0
    res_d = eng.abort(0)
    assert res_d.finish_reason == "abort"
    assert len(res_d.tokens) >= 1          # partial output returned
    assert eng.abort(99) is None
    rest = eng.drain()
    assert [r.uid for r in rest] == [1, 2]
    assert eng.sched.stats.aborted == 2


def test_abort_mid_prefill_releases_pages(tiny):
    """Regression: aborting a request whose chunked prefill has not
    finished must decref its mapped pages — before the fix the
    _PrefillJob kept the rows referenced and the pool leaked."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=160,
                  prefill_len=8, decode_batch=3, max_new_tokens=8)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=30).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt))
    eng.step()           # admit + first chunk only (8 of 30 tokens)
    assert 0 in [st.uid for st in eng.sched.table.active.values()]
    assert eng._prefill_jobs, "prefill must still be in flight"
    res = eng.abort(0)
    assert res.finish_reason == "abort" and len(res.tokens) == 0
    # only the parked pages stay hot: nothing leaked
    assert eng.pool.n_hot == eng.sc.decode_batch
    assert not eng._prefill_jobs
    # the engine still serves new work afterwards
    out = eng.generate([Request(uid=1, prompt=prompt.copy())])
    assert len(out[0].tokens) == 8


def test_abort_mid_prefill_with_prefix_match(tiny):
    """Same regression with prefix-matched pages in the row: the abort
    releases the reference the match took, so the shared pages go back
    to cold (revivable) instead of leaking hot."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=160,
                  prefill_len=8, decode_batch=3, max_new_tokens=8)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=30).astype(np.int32)
    eng.generate([Request(uid=0, prompt=prompt)])   # 3 blocks in the tree
    # shares 24 prompt tokens (3 full blocks), then diverges for 30 more
    # — the match leaves >1 chunk of prefill, so the job stays in flight
    tail = rng.integers(0, cfg.vocab, size=30).astype(np.int32)
    prompt2 = np.concatenate([prompt[:24], tail])
    eng.submit(Request(uid=1, prompt=prompt2))
    eng.step()
    job = next(iter(eng._prefill_jobs.values()), None)
    assert job is not None and job.matched_tokens == 24
    eng.abort(1)
    assert eng.pool.n_hot == eng.sc.decode_batch   # parked pages only
    assert eng.pool.n_cold == 3                    # match ref released


# ---------------------------------------------------------------------------
# Per-request sampling semantics
# ---------------------------------------------------------------------------
def test_mixed_greedy_temperature_parity(tiny):
    """A greedy lane co-batched with sampled lanes produces exactly the
    all-greedy output — filters and draws never touch greedy rows."""
    cfg, params = tiny
    reqs_greedy = _reqs(cfg, 3)
    ref = _engine(cfg, params).generate(reqs_greedy)

    sp = [None, SamplingParams(temperature=1.0, top_p=0.9, seed=3),
          SamplingParams(temperature=0.7, top_k=11, seed=4)]
    mixed = _engine(cfg, params).generate(_reqs(cfg, 3, params=sp))
    assert mixed[0].tokens.tolist() == ref[0].tokens.tolist()


def test_seed_determinism(tiny):
    cfg, params = tiny
    sp = [SamplingParams(temperature=1.0, seed=42) for _ in range(2)]
    eng = _engine(cfg, params)
    a = eng.generate(_reqs(cfg, 2, base_len=5, params=sp))
    b = eng.generate(_reqs(cfg, 2, base_len=5, params=sp))
    # same seed, same prompt → same tokens across runs; requests 0 and 1
    # share seed AND prompt-length-5? no — lengths differ by uid, so
    # only cross-run equality is asserted
    for x, y in zip(a, b):
        assert x.tokens.tolist() == y.tokens.tolist()

    sp2 = [SamplingParams(temperature=1.0, seed=43) for _ in range(2)]
    c = eng.generate(_reqs(cfg, 2, base_len=5, params=sp2))
    assert any(x.tokens.tolist() != y.tokens.tolist()
               for x, y in zip(a, c))


def test_stop_token_truncates_with_reason(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_new_tokens=12)
    probe = eng.generate(_reqs(cfg, 1))
    stop = int(probe[0].tokens[2])
    # greedy decode may emit the stop id before position 2 too — the
    # truncation point is its first occurrence
    cut = probe[0].tokens.tolist().index(stop)
    res = eng.generate(_reqs(
        cfg, 1, params=[SamplingParams(stop=(stop,), max_new_tokens=12)]))
    assert res[0].tokens[-1] == stop
    assert len(res[0].tokens) == cut + 1
    assert res[0].finish_reason == "stop"
    full = eng.generate(_reqs(cfg, 1))
    assert full[0].finish_reason == "length"


def test_top_k1_equals_greedy(tiny):
    """top_k=1 leaves only the argmax in the kept set, so a sampled
    lane at any temperature degenerates to the greedy stream."""
    cfg, params = tiny
    ref = _engine(cfg, params).generate(_reqs(cfg, 2))
    sp = [SamplingParams(temperature=1.3, top_k=1, seed=5 + i)
          for i in range(2)]
    res = _engine(cfg, params).generate(_reqs(cfg, 2, params=sp))
    for r, g in zip(res, ref):
        assert r.tokens.tolist() == g.tokens.tolist()


def test_top_p_one_equals_plain_temperature(tiny):
    """top_p=1.0 must be a true no-op filter: identical draws to the
    same seed with no nucleus cut and to top_k=vocab (the other no-op
    spelling) — while a real cut (top_p=0.5) moves the stream, proving
    the filter is live and the equality isn't vacuous."""
    cfg, params = tiny
    mk = lambda sp: _engine(cfg, params).generate(   # noqa: E731
        _reqs(cfg, 2, params=[sp, sp]))
    plain = mk(SamplingParams(temperature=0.9, seed=11))
    nucleus_off = mk(SamplingParams(temperature=0.9, top_p=1.0, seed=11))
    topk_full = mk(SamplingParams(temperature=0.9, top_k=cfg.vocab,
                                  seed=11))
    for a, b, c in zip(plain, nucleus_off, topk_full):
        assert a.tokens.tolist() == b.tokens.tolist()
        assert a.tokens.tolist() == c.tokens.tolist()
    cut = mk(SamplingParams(temperature=0.9, top_p=0.5, seed=11))
    assert any(a.tokens.tolist() != d.tokens.tolist()
               for a, d in zip(plain, cut))


def test_params_validation(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    bad = [SamplingParams(temperature=-1.0), SamplingParams(top_p=0.0),
           SamplingParams(top_k=-2), SamplingParams(max_new_tokens=-1)]
    for sp in bad:
        with pytest.raises(ValueError, match="request 0"):
            eng.submit(Request(uid=0,
                               prompt=np.zeros((3,), np.int32), params=sp))


def test_bucketed_matches_continuous_with_sampling(tiny):
    """The bucketed baseline and the continuous engine agree token-for-
    token per request under mixed per-request sampling params."""
    cfg, params = tiny
    sp = [None, SamplingParams(temperature=0.9, top_p=0.85, seed=5),
          SamplingParams(temperature=1.1, top_k=6)]
    reqs = lambda: _reqs(cfg, 3, base_len=5, params=sp)   # noqa: E731
    cont = _engine(cfg, params).generate(reqs(), seed=9)
    buck = _engine(cfg, params, scheduler="bucketed").generate(
        reqs(), seed=9)
    for c, b in zip(cont, buck):
        assert c.uid == b.uid
        assert c.tokens.tolist() == b.tokens.tolist()
        assert c.finish_reason == b.finish_reason
