"""Paged KV cache serving: block tables, prefix reuse, chunked prefill.

Acceptance-criteria coverage for PR 5: token-identical outputs paged vs
unpaged across KV dtypes × fused modes, chunked prefill beyond the
compiled chunk shape, prefix-cache reuse that provably skips prefill
work, and eviction under pool pressure — plus op-level paged
kernel/XLA-vs-oracle checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_lm
from repro.serve import Engine, Request, ServeConfig
from repro.serve.engine import chunk_ladder


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("phi3-mini-3.8b").reduced()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _reqs(cfg, n, base_len=5, budget=None, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=base_len + (i % 3))
                    .astype(np.int32),
                    max_new_tokens=budget[i] if budget else None)
            for i in range(n)]


def _engine(cfg, params, **kw):
    defaults = dict(max_len=64, decode_batch=3, max_new_tokens=6,
                    prefill_len=16, scheduler="continuous")
    defaults.update(kw)
    return Engine(params, cfg, ServeConfig(**defaults))


def _same_tokens(a, b, msg=""):
    for ra, rb in zip(a, b):
        assert ra.uid == rb.uid
        np.testing.assert_array_equal(ra.tokens, rb.tokens, err_msg=msg)


def _record_chunks(eng):
    """Wrap the engine's chunk program: returns the list each dispatch
    appends its (start, compiled width) to."""
    seen, chunk = [], eng._chunk

    def recorded(params, tokens, cache, row, start, *rest):
        seen.append((int(start), tokens.shape[1]))
        return chunk(params, tokens, cache, row, start, *rest)

    eng._chunk = recorded
    return seen


# ---------------------------------------------------------------------------
# Token parity: paged vs unpaged (acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prefill_len", [16, 64])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8", "int4"])
def test_paged_matches_unpaged_greedy(tiny, kv_dtype, prefill_len):
    """Paging the cache must be behaviorally invisible: identical greedy
    tokens for every KV container, with slot reuse (more requests than
    slots) in the mix. At prefill_len 64 the ladder is (64, 16), so the
    5–7-token prompts run at the narrower width."""
    cfg, params = tiny
    budget = {i: 3 + (i % 4) for i in range(6)}
    res_u = _engine(cfg, params, kv_dtype=kv_dtype,
                    prefill_len=prefill_len).generate(
        _reqs(cfg, 6, budget=budget))
    eng = _engine(cfg, params, kv_dtype=kv_dtype, paged=True, page_size=8,
                  prefill_len=prefill_len)
    res_p = eng.generate(_reqs(cfg, 6, budget=budget))
    _same_tokens(res_u, res_p, f"paged diverged at kv={kv_dtype}")
    assert eng.stats()["prefill_chunk_tokens_dispatched"] == 6 * 16


@pytest.mark.parametrize("fused", ["off", "auto", "on"])
def test_paged_fused_mode_parity_int4(tiny, fused):
    """The paged block-table read must agree across the legacy
    dequantize path, the fused-XLA gather lowering, and the
    scalar-prefetch Pallas kernel — on the packed4 container, whose
    paged writes exercise both the byte-pair chunk scatter and the
    single-nibble decode RMW."""
    cfg, params = tiny
    res_u = _engine(cfg, params, kv_dtype="int4", fused=fused).generate(
        _reqs(cfg, 4))
    res_p = _engine(cfg, params, kv_dtype="int4", fused=fused, paged=True,
                    page_size=8).generate(_reqs(cfg, 4))
    _same_tokens(res_u, res_p, f"paged int4 diverged at fused={fused}")


def test_paged_streaming_submit_step_drain(tiny):
    """Late submissions join mid-flight; streaming matches generate()."""
    cfg, params = tiny
    eng = _engine(cfg, params, decode_batch=2, paged=True, page_size=8)
    reqs = _reqs(cfg, 4)
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    done = []
    for _ in range(3):
        done.extend(eng.step())
    eng.submit(reqs[2])
    eng.submit(reqs[3])
    done.extend(eng.drain())
    done.sort(key=lambda r: r.uid)
    assert [r.uid for r in done] == [0, 1, 2, 3]
    assert all(len(r.tokens) == 6 for r in done)
    res_u = _engine(cfg, params, decode_batch=2).generate(_reqs(cfg, 4))
    _same_tokens(res_u, done)


# ---------------------------------------------------------------------------
# Chunked prefill (prompt > prefill_len)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prefill_len,chunks", [
    (16, [(0, 16), (16, 16), (32, 16)]),      # ladder (16,): 16 // 4 < page
    (32, [(0, 32), (32, 8)]),                 # ladder (32, 8)
])
def test_chunked_prefill_long_prompt_exact(tiny, prefill_len, chunks):
    """A 40-token prompt through a narrower chunk shape: chunks start
    every prefill_len tokens at full width and only the last narrows,
    tokens identical to the bucketed scheduler's native-length prefill
    (the unpaged continuous engine rejects this prompt outright). f32
    KV: chunked attention over stored context is then mathematically
    exact, so cross-scheduler greedy identity is a hard guarantee."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    mk = lambda: [Request(uid=0, prompt=prompt.copy(),  # noqa: E731
                          max_new_tokens=6)]
    kw = dict(max_len=96, decode_batch=2, prefill_len=prefill_len,
              kv_dtype="f32")
    with pytest.raises(ValueError, match="prefill"):
        _engine(cfg, params, **kw).submit(mk()[0])
    res_b = _engine(cfg, params, scheduler="bucketed", **kw).generate(mk())
    eng = _engine(cfg, params, paged=True, page_size=8, **kw)
    seen = _record_chunks(eng)
    res_p = eng.generate(mk())
    _same_tokens(res_b, res_p, "chunked prefill diverged from bucketed")
    assert seen == chunks
    st = eng.stats()
    assert st["prefill_chunks"] == len(chunks)  # ceil(40 / prefill_len)
    assert st["prefill_tokens_computed"] == 40
    assert st["prefill_chunk_tokens_dispatched"] == sum(w for _, w in chunks)


@pytest.mark.parametrize("kv_dtype", ["f32", "int4"])
def test_chunk_overhang_pad_writes_dropped(tiny, kv_dtype):
    """Regression: when the final chunk overhangs the block table
    (start + chunk_len > n_blocks·page_size), its pad-lane writes used
    to clamp into the row's last block and collide with valid prompt
    slots — an unordered duplicate-index scatter that let pad garbage
    replace real KV. Pad lanes must be dropped: max_len=24, page=8,
    chunk=16, 20-token prompt (final chunk spans [16, 32) over a
    24-slot table) has to reproduce the bucketed tokens exactly (f32)
    and the int4 byte-pair path likewise must not corrupt."""
    cfg, params = tiny
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab, size=20).astype(np.int32)
    mk = lambda: [Request(uid=0, prompt=prompt.copy(),  # noqa: E731
                          max_new_tokens=3)]
    kw = dict(decode_batch=2, prefill_len=16, kv_dtype=kv_dtype)
    res_over = _engine(cfg, params, paged=True, page_size=8, max_len=24,
                       **kw).generate(mk())
    if kv_dtype == "f32":
        # exact math: the bucketed native-length prefill is the oracle
        ref = _engine(cfg, params, scheduler="bucketed", max_len=24,
                      **kw).generate(mk())
    else:
        # quantized chunked context reads legitimately differ from the
        # bucketed exact prefill; the corruption-isolating oracle is the
        # same paged pipeline on a table the final chunk does NOT
        # overhang (max_len 32 ⇒ 4 blocks ⊇ chunk [16, 32))
        ref = _engine(cfg, params, paged=True, page_size=8, max_len=32,
                      **kw).generate(mk())
    _same_tokens(ref, res_over, f"overhang pad writes corrupted kv={kv_dtype}")


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_chunked_prefill_long_prompt_fused_parity(tiny, kv_dtype):
    """Quantized KV + chunked prefill: later chunks legitimately read
    *stored* (quantized) context where a one-shot prefill reads exact
    activations, so cross-scheduler greedy identity is not guaranteed at
    4 bits. The hard criterion is self-parity: the three attention
    lowerings (legacy dequant, fused-XLA gather, Pallas paged kernel)
    run the same quantization pipeline and must emit identical tokens —
    and the chunk accounting must show the prompt streamed in chunks."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    mk = lambda: [Request(uid=0, prompt=prompt.copy(),  # noqa: E731
                          max_new_tokens=6)]
    kw = dict(max_len=96, decode_batch=2, prefill_len=16, kv_dtype=kv_dtype,
              paged=True, page_size=8)
    outs = {}
    for fused in ("off", "auto", "on"):
        eng = _engine(cfg, params, fused=fused, **kw)
        outs[fused] = eng.generate(mk())
        assert eng.stats()["prefill_chunks"] == 3
    _same_tokens(outs["off"], outs["auto"],
                 f"kv={kv_dtype} fused=auto diverged from off")
    _same_tokens(outs["off"], outs["on"],
                 f"kv={kv_dtype} fused=on diverged from off")


def test_chunked_prefill_interleaves_with_decode(tiny):
    """A long prompt admitted mid-flight advances one chunk per engine
    step while the resident request keeps decoding — and neither
    request's tokens change vs. serial execution."""
    cfg, params = tiny
    rng = np.random.default_rng(8)
    long_prompt = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    short = _reqs(cfg, 1, base_len=6)[0]
    short.max_new_tokens = 10

    kw = dict(max_len=96, decode_batch=2, prefill_len=16, kv_dtype="f32")
    eng = _engine(cfg, params, paged=True, page_size=8, **kw)
    eng.submit(short)
    for _ in range(2):
        eng.step()                     # short is decoding
    eng.submit(Request(uid=1, prompt=long_prompt.copy(), max_new_tokens=4))
    done = eng.drain()
    done.sort(key=lambda r: r.uid)
    assert [len(r.tokens) for r in done] == [10, 4]

    # serial references: each request alone produces the same tokens
    ref_s = _engine(cfg, params, paged=True, page_size=8, **kw).generate(
        [Request(uid=0, prompt=short.prompt, max_new_tokens=10)])
    ref_l = _engine(cfg, params, paged=True, page_size=8, **kw).generate(
        [Request(uid=1, prompt=long_prompt.copy(), max_new_tokens=4)])
    np.testing.assert_array_equal(done[0].tokens, ref_s[0].tokens)
    np.testing.assert_array_equal(done[1].tokens, ref_l[0].tokens)


# ---------------------------------------------------------------------------
# Chunk ladder: a prompt's final chunk at the narrowest width that holds it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_len,page_size,ladder", [
    (4096, 64, (4096, 1024, 256, 64)),
    (64, 8, (64, 16)),
    (128, 8, (128, 32, 8)),
    (16, 8, (16,)),                           # the quarter is under a page
    (96, 16, (96,)),                          # the quarter is 1.5 pages
    (192, 16, (192, 48)),                     # 12 is under a page
])
def test_chunk_ladder(chunk_len, page_size, ladder):
    assert chunk_ladder(chunk_len, page_size) == ladder


def test_chunk_ladder_follows_the_engine_geometry(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=128,
                  prefill_len=64)
    assert eng._chunk_widths == (64, 16)
    assert eng._chunk_len == 64
    assert _engine(cfg, params)._chunk_widths == (16,)   # unpaged


def test_prefix_hit_final_chunk_narrows(tiny):
    """A question over a cached document: 40 document tokens (5 pages)
    hit the prefix cache, the 6 fresh tokens run in one 16-wide chunk
    instead of a 64-wide one, and the greedy tokens are those of the
    unpaged engine (f32 KV: the stored context is exact)."""
    cfg, params = tiny
    rng = np.random.default_rng(21)
    doc = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    question = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    prompt = np.concatenate([doc, question])
    kw = dict(max_len=128, decode_batch=2, prefill_len=64, kv_dtype="f32",
              max_new_tokens=6)
    eng = _engine(cfg, params, paged=True, page_size=8, **kw)
    eng.generate([Request(uid=0, prompt=doc.copy(), max_new_tokens=1)])
    seen = _record_chunks(eng)
    res_p = eng.generate([Request(uid=1, prompt=prompt.copy())])
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 40
    assert seen == [(40, 16)]
    assert st["prefill_tokens_computed"] == 6
    assert st["prefill_chunk_tokens_dispatched"] == 16
    res_u = _engine(cfg, params, **kw).generate(
        [Request(uid=1, prompt=prompt.copy())])
    _same_tokens(res_u, res_p, "narrowed final chunk diverged from unpaged")


def test_narrow_chunk_budget_admits_more(tiny):
    """The token budget charges the width dispatched: a budget of 64 + 1
    has no room for a 64-wide chunk (+1 lane) beside a decoding lane,
    but admits a short prompt's 16-wide chunk there."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=128,
                  prefill_len=64, decode_batch=2, max_step_tokens=64 + 1,
                  kv_dtype="f32")
    reqs = _reqs(cfg, 2, base_len=5)
    eng.submit(reqs[0])
    eng.step()                                # uid 0 decoding
    eng.submit(reqs[1])
    d0 = eng.stats()["prefill_chunk_tokens_dispatched"]
    eng.step()                                # 1 lane + 16 + 1 <= 65
    assert eng.stats()["prefill_chunk_tokens_dispatched"] - d0 == 16
    assert eng.sched.table.n_active == 2
    assert eng.stats()["budget_deferred_admissions"] == 0


def test_warmup_compiles_every_ladder_width(tiny):
    """warmup() compiles and runs every ladder width, so serving prompts
    that take each width (with and without a prefix hit) compiles
    nothing afterwards."""
    from jax import monitoring
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, max_len=128,
                  prefill_len=64, decode_batch=2, max_new_tokens=3,
                  telemetry=True)
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(ev)
        if "backend_compile" in ev else None)
    rng = np.random.default_rng(5)
    doc = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 30, 70)] + [np.concatenate([doc, doc[:3]])]
    eng.warmup()
    assert eng.stats()["compiled_shapes_prefill_chunk"] == 2
    n0 = len(compiles)
    eng.generate([Request(uid=0, prompt=doc.copy())])
    eng.generate([Request(uid=i + 1, prompt=p)
                  for i, p in enumerate(prompts)])
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 40
    # 5 → 16; 30 → 64; 70 → 64 + 16; 3 fresh after the hit → 16
    assert st["prefill_chunk_tokens_dispatched"] == 16 + 64 + 80 + 16
    assert len(compiles) == n0, compiles[n0:]
    assert st["compiled_shapes_prefill_chunk"] == 2


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------
def test_prefix_reuse_skips_work_and_preserves_tokens(tiny):
    """Shared system prompt: later requests map the donor's pages in
    (hit rate > 0, computed prefill tokens drop) and greedy outputs are
    identical to the same engine with reuse disabled."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    sys_p = rng.integers(0, cfg.vocab, size=24).astype(np.int32)

    def mk():
        r = np.random.default_rng(4)
        return [Request(uid=i, prompt=np.concatenate(
            [sys_p, r.integers(0, cfg.vocab, size=6).astype(np.int32)]),
            max_new_tokens=4) for i in range(5)]

    kw = dict(max_len=96, decode_batch=2, prefill_len=16, kv_dtype="f32",
              paged=True, page_size=8)
    eng = _engine(cfg, params, **kw)
    res = eng.generate(mk())
    eng_no = _engine(cfg, params, prefix_cache=False, **kw)
    res_no = eng_no.generate(mk())
    _same_tokens(res, res_no, "prefix reuse changed outputs")

    st, st_no = eng.stats(), eng_no.stats()
    assert st["prefix_hit_blocks"] > 0
    assert st["prefix_hit_rate"] > 0
    assert (st["prefill_tokens_computed"]
            < st_no["prefill_tokens_computed"])
    assert st_no["prefill_tokens_computed"] == st_no["prompt_tokens_total"]


def test_prefix_cache_warm_across_generate_calls(tiny):
    """The radix tree persists across generate() runs: a repeat of the
    same workload prefills almost nothing and still emits the same
    tokens (greedy determinism criterion, paged flavor)."""
    cfg, params = tiny
    eng = _engine(cfg, params, paged=True, page_size=8, kv_dtype="f32",
                  max_len=96, prefill_len=16)
    reqs = lambda: _reqs(cfg, 3, base_len=17)  # noqa: E731  (2 full blocks)
    a = eng.generate(reqs())
    cold = eng.stats()["prefill_tokens_computed"]
    b = eng.generate(reqs())
    warm = eng.stats()["prefill_tokens_computed"]
    _same_tokens(a, b, "warm prefix cache changed outputs")
    assert warm < cold


def test_eviction_under_pool_pressure(tiny):
    """A pool too small to retain every retired prompt must evict
    (stats count it) and still produce exactly the big-pool tokens."""
    cfg, params = tiny
    budget = {i: 6 for i in range(6)}
    reqs = lambda: _reqs(cfg, 6, base_len=24, budget=budget, seed=5)  # noqa: E731
    kw = dict(max_len=64, decode_batch=2, prefill_len=16, kv_dtype="f32",
              paged=True, page_size=8)
    big = _engine(cfg, params, **kw).generate(reqs())
    eng = _engine(cfg, params, n_pages=12, **kw)   # nb=8 + 2 parked + 2
    res = eng.generate(reqs())
    _same_tokens(big, res, "eviction changed outputs")
    assert eng.stats()["evictions"] > 0


def test_pool_exhaustion_defers_admission(tiny):
    """With pages for only one resident request, the second request
    waits for the first to retire instead of deadlocking or corrupting;
    everything completes with the bucketed scheduler's tokens."""
    cfg, params = tiny
    budget = {0: 4, 1: 4}
    # 30/31-token prompts need 5 blocks each; 10 pages − 2 parked = 8
    # free, so only one request fits at a time
    reqs = lambda: _reqs(cfg, 2, base_len=30, budget=budget, seed=6)  # noqa: E731
    kw = dict(max_len=64, decode_batch=2, prefill_len=16, kv_dtype="f32")
    eng = _engine(cfg, params, paged=True, page_size=8, n_pages=10,
                  prefix_cache=False, **kw)
    res = eng.generate(reqs())
    assert [len(r.tokens) for r in res] == [4, 4]
    assert eng.stats()["occupancy"] <= 0.75  # the lanes never ran together
    res_b = _engine(cfg, params, scheduler="bucketed", **kw).generate(reqs())
    _same_tokens(res_b, res)


# ---------------------------------------------------------------------------
# Guards + op-level paged parity
# ---------------------------------------------------------------------------
def test_paged_rejects_unsupported_arch():
    """Recurrent / local-window stacks have no block-sharing story."""
    cfg = get_config("recurrentgemma-9b").reduced()
    params = init_lm(jax.random.PRNGKey(1), cfg)
    with pytest.raises(ValueError, match="paged"):
        _engine(cfg, params, paged=True)


def test_paged_needs_continuous_scheduler(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="continuous"):
        _engine(cfg, params, paged=True, scheduler="bucketed")


@pytest.mark.parametrize("container", ["f32", "int8", "int4"])
@pytest.mark.parametrize("kernel", [False, True])
def test_paged_op_matches_oracle(container, kernel):
    """decode_attention_op(block_table=...) — both the XLA gather
    lowering and the scalar-prefetch Pallas kernel — against the paged
    oracle, on a shuffled block table with ragged row positions."""
    from repro.kernels.ops import decode_attention_op
    from repro.kernels.ref import decode_attention_ref
    from repro.quant.mxint import pack_codes_4bit

    rng = np.random.default_rng(11)
    b, kv, g, hd, ps, nb, pages = 3, 2, 2, 16, 8, 4, 14
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)), jnp.float32)
    q_pos = jnp.asarray([3, 17, 31], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(nb * ps)[None],
                             (b, nb * ps)).astype(jnp.int32)
    bt = jnp.asarray(rng.permutation(pages)[:b * nb].reshape(b, nb),
                     jnp.int32)
    ks = vs = None
    if container == "f32":
        k = jnp.asarray(rng.normal(size=(pages, kv, ps, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(pages, kv, ps, hd)), jnp.float32)
    else:
        hi = 128 if container == "int8" else 8
        kc = rng.integers(-hi + 1, hi, size=(pages, kv, ps, hd))
        vc = rng.integers(-hi + 1, hi, size=(pages, kv, ps, hd))
        ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(pages, kv, ps)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(pages, kv, ps)),
                         jnp.float32)
        k = jnp.asarray(kc, jnp.int8)
        v = jnp.asarray(vc, jnp.int8)
        if container == "int4":
            k, v = pack_codes_4bit(k), pack_codes_4bit(v)
    ref = decode_attention_ref(q, k, v, q_pos, k_pos, ks, vs,
                               block_table=bt)
    out = decode_attention_op(q, k, v, q_pos, k_pos, k_scale=ks, v_scale=vs,
                              kernel=kernel, block_table=bt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_hbm_smaller_than_slot_rows(tiny):
    """The sized-down pool undercuts the contiguous slot cache: the
    structural memory win paging exists for."""
    from repro.serve import PagedKVCache, SlotKVCache
    cfg, _ = tiny
    dense = SlotKVCache(cfg, 8, 512, "int8")
    # typical mix: half the lanes short-lived — pool sized well under
    # full residency (8 lanes × 64 blocks) still serves the workload
    paged = PagedKVCache(cfg, 8, 512, "int8", page_size=8, n_pages=300)
    assert paged.hbm_bytes() < dense.hbm_bytes()
