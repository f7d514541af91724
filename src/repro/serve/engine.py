"""Serving engine: continuous batching over a (quantized) Q + LR model.

The engine serves the paper's deployment artifact — a ``W ≈ Q + LR``
model — through the same forward code paths the dry-run lowers at pod
scale, under two schedulers:

  * ``continuous`` (default, production): a **slot-based KV cache**
    (``serve.slots``) gives every batch row its own write position and
    valid-length mask, so requests are admitted into free slots
    *mid-flight*: prefill-on-admit scatters a freshly prefilled row into
    the live cache while the other slots keep decoding. Per-request
    ``max_new_tokens`` / EOS retire a slot the moment its request
    finishes, and the next queued request takes the lane on the same
    step. One (slots, 1) decode shape plus the prefill shapes —
    one (1, prefill_len) prefill, or under the paged cache the chunk
    ladder (the chunk width and its quarters down to one page; a
    prompt's final chunk takes the narrowest that holds it) —
    regardless of the prompt-length mix (prompts are right-padded and
    masked, never re-bucketed).
  * ``bucketed`` (baseline): the old dry-run-grade scheduler — requests
    grouped by identical prompt length, each bucket padded to
    ``decode_batch`` and decoded to its slowest member. Kept for A/B
    benchmarking (``benchmarks/serve_throughput.py``).

Also here: **int8 KV** (``kv_dtype="int8"``) halves cache HBM — the
quantization-native option that makes 32k-context MHA models fit — and
**int4 KV** (``kv_dtype="int4"``) halves it again via the packed4
nibble container (two slots per byte, unpacked inside the flash-decode
kernel), doubling the servable slots or context at fixed memory; plus
per-request latency metrics (TTFT, end-to-end latency) and scheduler
occupancy counters. The ``fused`` switch routes every quantized
projection in prefill *and* per-step decode through the fused Q + LR
matmul (``repro.kernels.ops.qlr_matmul``) **and** per-step decode
attention through the flash-decode path
(``repro.kernels.ops.decode_attention_op``: Pallas kernel on TPU,
fused-XLA elsewhere — int8 KV codes are read straight from the
head-major cache pages and dequantized in VMEM / on the score planes),
so neither the dequantized weight nor the dequantized cache ever
round-trips HBM. MLA models additionally get their absorbed decode
projections (W_uk / W_uv) materialized once per engine session instead
of once per token (see ``absorbed_params`` below).

Request-path API (one surface across Python, CLI, and HTTP —
``serve.http`` speaks OpenAI over exactly these calls):

  * per-request :class:`~repro.serve.sampling.SamplingParams` on
    ``Request.params`` — temperature / top-p / top-k / seed / stop ids /
    max_new_tokens; mixed greedy+sampled batches decode together, each
    lane drawing from its own counter-based PRNG stream so output is
    independent of scheduling. ``ServeConfig.temperature`` / ``eos_id``
    are *defaults* only.
  * ``Result.finish_reason`` ∈ ``"stop" | "length" | "abort"``.
  * ``abort(uid)`` cancels a request anywhere in its lifecycle — queued,
    mid-chunked-prefill (pages decref'd, prefix match released), or
    decoding — and frees its slot immediately.
  * ``ServeConfig.max_step_tokens`` arms the token-budget step
    scheduler: per step, prefill tokens (chunk dispatches at compiled
    width) + decode lanes stay ≤ the budget, so a burst of long-prompt
    admissions cannot stall live decode lanes (bounded p95 ITL);
    ``max_pages_per_request`` and ``free_watermark`` add per-request
    page quotas and ahead-of-demand cold-set eviction under the paged
    cache.

API: ``submit()`` / ``step()`` / ``drain()`` for streaming use;
``generate()`` runs a whole batch of requests through either scheduler.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.constraints import validate_page_size
from repro.models import (Ctx, decode_step, init_cache, prefill,
                          prefill_chunk, verify_chunk)
from repro.models.attention import absorb_mla_weights
from repro.serve.pages import PagedKVCache, PagePool
from repro.serve.sanitizer import Sanitizer
from repro.serve.prefix import RadixPrefixCache
from repro.serve.sampling import (TOP_LOGPROBS, SamplingParams, lane_seed,
                                  sample_tokens)
from repro.serve.scheduler import (ContinuousScheduler, SchedulerStats,
                                   StepBudget)
from repro.serve.slots import KV_DTYPES, SlotKVCache
from repro.serve.telemetry import (NULL_TELEMETRY, MetricsRegistry,
                                   Telemetry, install_gc_hook, log_buckets)


# --------------------------------------------------------------------------
# MLA absorbed-weight cache: ``mla_step`` folds q / the attention output
# through W_uk / W_uv each token; materializing those dense projections
# from the quantized Q+LR params *inside* the compiled decode step would
# re-run dequant + the L·R product per token. Absorb once per params
# tree instead, keyed on identity (repeat Engine constructions over the
# same quantized model — A/B benchmark sweeps — reuse the absorption).
# --------------------------------------------------------------------------
# single entry: consecutive engines over the same params (mode/kv-dtype
# A/B sweeps) share the absorption; a new params tree evicts the old one
# immediately, so at most one model's absorbed weights stay resident.
# Deliberate trade-off: the entry outlives its engines (that is what
# makes A/B sweeps hit), retaining at most one model until the next
# absorption or a non-MLA engine construction; call
# release_absorbed_params() to free it eagerly.
_absorb_cache: Optional[tuple] = None  # (params, absorbed)


def _params_have_lowrank(tree) -> bool:
    """True when any quantized matrix in the tree carries a non-empty
    low-rank correction (an ``l`` leaf with rank > 0). Decides the
    speculative verify's storage mode: without LR slivers the Q-only
    draft IS the full model, so the drafts' step-graph KV writes are
    already exact and verify can stay read-only."""
    if isinstance(tree, dict):
        ll = tree.get("l")
        if hasattr(ll, "shape") and ll.shape and ll.shape[-1] > 0:
            return True
        return any(_params_have_lowrank(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return any(_params_have_lowrank(v) for v in tree)
    return False


def _absorb_mla_tree(p):
    """Copy of the params tree with every MLA mixer (any dict carrying
    ``w_uk``/``w_uv``) augmented with its dense absorbed projections.
    Scan-stacked group mixers pass through with their leading dim."""
    if isinstance(p, dict):
        if "w_uk" in p and "w_uv" in p:
            return absorb_mla_weights(p)
        return {k: _absorb_mla_tree(v) for k, v in p.items()}
    if isinstance(p, list):
        return [_absorb_mla_tree(v) for v in p]
    if isinstance(p, tuple):
        return tuple(_absorb_mla_tree(v) for v in p)
    return p


def absorbed_params(params):
    """Identity-cached :func:`_absorb_mla_tree` (single entry)."""
    global _absorb_cache
    if _absorb_cache is not None and _absorb_cache[0] is params:
        return _absorb_cache[1]
    out = _absorb_mla_tree(params)
    _absorb_cache = (params, out)
    return out


def release_absorbed_params() -> None:
    """Drop the cached absorption so the old model's params + dense
    W_uk/W_uv become collectable. Called when an engine is built over a
    non-MLA model (the cache can only be stale then); live MLA engines
    keep their own reference to the absorbed tree."""
    global _absorb_cache
    _absorb_cache = None


def chunk_ladder(chunk_len: int, page_size: int) -> Tuple[int, ...]:
    """Compiled widths a paged engine dispatches prefill chunks at,
    widest first: ``chunk_len // 4**i`` for as long as the width is at
    least one page and a whole number of pages (``chunk_len`` itself is
    always the first). A prompt's final chunk runs at the narrowest
    width that holds its remaining tokens, so a short question over a
    cached document does not pay for a full-width chunk."""
    widths = [chunk_len]
    while True:
        w = chunk_len // 4 ** len(widths)
        if w < page_size or w % page_size:
            return tuple(widths)
        widths.append(w)


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512               # cache slots (prompt + generation)
    decode_batch: int = 8            # decode lanes (= slots, continuous)
    max_new_tokens: int = 64
    eos_id: int = -1                 # -1: never stop early. DEFAULT only:
    # per-request SamplingParams.stop ids extend it
    kv_dtype: str = "bf16"           # bf16 | f32 | int8 | int4
    temperature: float = 0.0         # 0 = greedy. DEFAULT only: a
    # request's SamplingParams.temperature overrides per lane (the old
    # engine-global knob is deprecated as anything but a fallback)
    compute_dtype: str = "f32"
    scheduler: str = "continuous"    # continuous | bucketed
    prefill_len: Optional[int] = None  # compiled prompt pad length; under
    # --paged this is the *chunk* width, no longer a prompt-length cap,
    # and the top width of the chunk ladder a final chunk narrows along
    seed: int = 0                    # sampling stream for submit()/step()
    fused: str = "auto"              # Q+LR matmul path: auto | on | off
    # --- paged KV cache (serve.pages / serve.prefix) ---
    paged: bool = False              # block-granular pages + block tables
    page_size: int = 64              # logical slots per page (even; on real
    # TPU must meet the Mosaic sublane tile: ≥32, ≥64 for int4 — the
    # default clears both, so a default paged engine builds on the chip
    # for every kv_dtype)
    n_pages: Optional[int] = None    # pool size; default sizes for full
    # residency of every lane + one request of prefix-retention headroom
    prefix_cache: bool = True        # radix-tree automatic prefix reuse
    # --- token-budget step scheduler ---
    max_step_tokens: Optional[int] = None  # per-step cap on prefill
    # tokens (chunk/prefill dispatches at their compiled width) + decode
    # lanes; None = unbudgeted. Must be >= the compiled prefill width + 1
    # so an admission can always make progress on an idle engine
    max_pages_per_request: Optional[int] = None  # paged: hard page quota
    # per request — clamps the decode budget so prompt+generation never
    # maps more than this many pages (fairness under pool pressure)
    free_watermark: float = 0.0      # paged: fraction of the pool kept
    # free by evicting cold prefix pages ahead of demand each step
    # (0 = evict only when an allocation would fail)
    # --- telemetry (serve.telemetry) ---
    telemetry: bool = False          # request/step tracing + latency
    # histograms + compile tracking; the metrics registry itself is
    # always live (stats()/metrics()/prometheus() are one snapshot)
    trace_sync: bool = False         # block_until_ready fence after device
    # dispatch so device time lands in the phase that launched it
    profile_dir: Optional[str] = None  # arm jax.profiler capture here
    profile_steps: int = 20          # engine steps to capture when armed
    # --- self-speculative decoding (Q-only draft, Q+LR verify) ---
    speculative: bool = False        # draft with the quantized base alone
    # (the LR sliver sliced to rank 0 — same resident weights, strictly
    # less work per token), then score spec_k tokens in one full-model
    # chunk dispatch; token-identical to non-speculative decode
    spec_k: int = 4                  # tokens scored per verify chunk
    # (1 fed last-token + spec_k-1 drafts); >= 2
    # --- runtime invariant sanitizer (serve.sanitizer) ---
    sanitize: bool = False           # audit page refcounts, block
    # tables, pos/slot_pos and int4 alignment after every step();
    # read-only (token-identical) but host-syncing — CI smokes and
    # debugging, not production
    # --- accuracy-drift monitor (repro.obs quantization observability) ---
    drift_monitor: bool = False      # sampled shadow comparison of the
    # serving logits against a reference lowering of the same quantized
    # params: per-lane KL / top-1 agreement / max-|Δlogit| histograms +
    # always-cheap NaN/inf guard counters. Read-only (token-identical);
    # costs one extra decode dispatch per sampled step
    drift_sample_rate: float = 0.05  # fraction of plain decode steps
    # shadow-compared (deterministic in the step counter, never in the
    # tokens); 1.0 = every step
    drift_ref_fused: str = "off"     # fused mode of the reference
    # lowering (auto | on | off); "off" = dequant-then-matmul, the
    # ungrouped ground-truth path the kernels are verified against


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (L,) int32
    max_new_tokens: Optional[int] = None  # deprecated shim — prefer
    # params.max_new_tokens; kept so pre-SamplingParams callers keep
    # working (params wins when both are set)
    t_submit: float = 0.0
    params: Optional[SamplingParams] = None  # per-request sampling/stop;
    # submit() resolves None fields against the ServeConfig defaults


@dataclasses.dataclass
class Result:
    """Timings are ``None`` when the underlying event never happened —
    a request retired without decoding (``max_new_tokens=0``) reports
    ``decode_s=None``/``ttft_s=None``, distinguishable from "decoded in
    ~0 seconds"; a missing submit timestamp yields ``latency_s=None``
    instead of a silent 0.0."""
    uid: int
    tokens: np.ndarray               # generated tokens (without prompt)
    prefill_s: Optional[float] = None  # prefill wall time for this request
    decode_s: Optional[float] = None   # first token → last token
    ttft_s: Optional[float] = None     # submit → first token
    latency_s: Optional[float] = None  # submit → done
    finish_reason: Optional[str] = None  # "stop" (EOS / stop id, token
    # included in tokens) | "length" (budget exhausted) | "abort"


@dataclasses.dataclass
class _PrefillJob:
    """A paged admission mid-chunked-prefill: the slot is allocated and
    its block table mapped, but the prompt is only prefilled up to
    ``next`` — one chunk advances per engine step, interleaved with the
    other slots' decode."""
    req: Request
    state: object                    # the scheduler's SlotState
    next: int                        # first not-yet-prefilled position
    matched_tokens: int              # prefix-cache tokens skipped
    prepaid: bool = False            # this step's chunk already charged
    # to the token budget at admission (don't double-charge)


class Engine:
    def __init__(self, params, cfg: ModelConfig, sc: ServeConfig,
                 extra_inputs: Optional[Dict[str, np.ndarray]] = None):
        if sc.scheduler not in ("continuous", "bucketed"):
            raise ValueError(f"unknown scheduler {sc.scheduler!r}")
        if sc.fused not in ("auto", "on", "off"):
            raise ValueError(f"unknown fused mode {sc.fused!r}")
        if sc.kv_dtype not in KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {sc.kv_dtype!r} "
                             f"(choose from {sorted(KV_DTYPES)})")
        if sc.paged:
            if sc.scheduler != "continuous":
                raise ValueError("paged KV needs scheduler='continuous'")
            unsupported = [k for k in cfg.block_pattern if k != "attn"]
            if (unsupported or cfg.attn_kind == "mla"
                    or cfg.is_encoder_decoder or cfg.n_vision_tokens):
                raise ValueError(
                    f"paged KV cache supports pure full-GQA-attention "
                    f"stacks (got pattern={cfg.block_pattern}, "
                    f"attn_kind={cfg.attn_kind!r}): recurrent states, MLA "
                    f"latents and encoder memories have no block-sharing "
                    f"story yet")
        if sc.speculative:
            if sc.scheduler != "continuous":
                raise ValueError("speculative decoding needs "
                                 "scheduler='continuous'")
            if sc.spec_k < 2:
                raise ValueError(
                    f"spec_k={sc.spec_k} must be >= 2 — one Q-only draft "
                    f"token plus the verify model's own next token")
            unsupported = [k for k in cfg.block_pattern if k != "attn"]
            if (unsupported or cfg.attn_kind == "mla"
                    or cfg.is_encoder_decoder or cfg.n_vision_tokens):
                raise ValueError(
                    f"speculative decoding verifies through the chunked "
                    f"attention path and needs a pure full-GQA-attention "
                    f"decoder (got pattern={cfg.block_pattern}, "
                    f"attn_kind={cfg.attn_kind!r})")
        if sc.drift_ref_fused not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown drift_ref_fused {sc.drift_ref_fused!r}")
        if sc.drift_monitor:
            if sc.scheduler != "continuous":
                raise ValueError("drift_monitor shadows the continuous "
                                 "engine's decode dispatch — it needs "
                                 "scheduler='continuous'")
            if not 0.0 < sc.drift_sample_rate <= 1.0:
                raise ValueError(
                    f"drift_sample_rate={sc.drift_sample_rate} must be "
                    f"in (0, 1]")
        # absorb MLA decode weights once per engine session (identity-
        # cached across engines; switching to a non-MLA model frees any
        # previous model's cached absorption)
        if cfg.attn_kind == "mla":
            self.params = absorbed_params(params)
        else:
            self.params = params
            release_absorbed_params()
        self.cfg = cfg
        self.sc = sc
        self.extra = extra_inputs or {}
        # fused="auto" serves the Q+LR decomposition through the Pallas
        # kernels on TPU and the fused-XLA lowering elsewhere; "on"
        # forces the kernels (interpret off-TPU — validation runs).
        # use_pallas follows the resolved mode: whenever the matmul runs
        # as a kernel, prefill attention takes the flash kernel too —
        # the engine is inference-only, so the kernels' lack of a VJP
        # cannot bite here.
        from repro.models.linear import fused_mode
        ctx = Ctx(compute_dtype=KV_DTYPES[sc.compute_dtype], fused=sc.fused)
        ctx.use_pallas = fused_mode(ctx) == "kernel"
        self.ctx = ctx
        # the registry is always live (stats()/metrics()/prometheus()
        # are snapshots of it); the *recorder* — tracing, step-phase
        # histograms, compile tracking — is the recording-free singleton
        # unless telemetry is on, so the hot loop pays only the profiler
        # annotation per call site when disabled
        self.registry = MetricsRegistry()
        if sc.telemetry or sc.profile_dir:
            self.tel = Telemetry(registry=self.registry, sync=sc.trace_sync,
                                 profile_dir=sc.profile_dir,
                                 profile_steps=sc.profile_steps)
        else:
            self.tel = NULL_TELEMETRY
        self._steps = 0                  # serve/step's step_num
        # collector pauses: process-wide, published as deltas from the
        # last reset_stats()
        self._gc = install_gc_hook()
        self._gc_base = self._gc.totals()
        self.prefill_len = sc.prefill_len or sc.max_len
        if self.prefill_len > sc.max_len:
            raise ValueError(
                f"prefill_len={self.prefill_len} exceeds max_len="
                f"{sc.max_len}: the prefill shape must fit the cache")
        self._n_vis = cfg.n_vision_tokens or 0

        cdt = KV_DTYPES[sc.kv_dtype]
        self._init_cache = lambda: init_cache(
            cfg, sc.decode_batch, sc.max_len, dtype=cdt)

        ctx = self.ctx

        # per-lane sampling: `lanes` is a (temps, top_ps, top_ks, seeds,
        # idxs) tuple of (B,) arrays. PRNG keys are derived inside the
        # jit from (seed, token index) — counter-based, so a lane's draw
        # never depends on scheduling, batch composition, or step count.
        # `want_lp` is *static*: the logprob report (log_softmax + top-k)
        # is only traced into the graph when a live lane asked for it,
        # so the default hot path compiles exactly as before
        def _lp(lg, tok, want_lp):
            if not want_lp:
                return None
            lp = jax.nn.log_softmax(lg, axis=-1)
            chosen = jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]
            top_lp, top_ids = jax.lax.top_k(lp, TOP_LOGPROBS)
            return chosen, top_lp, top_ids

        def _sample(logits, lanes, want_lp):
            lg = logits[:, -1].astype(jnp.float32)
            tok = sample_tokens(lg, *lanes)
            return (tok[:, None], _lp(lg, tok, want_lp))

        def _prefill(params, batch, cache, lengths, lanes, want_lp):
            logits, cache = prefill(ctx, params, batch, cfg, cache,
                                    lengths=lengths)
            return _sample(logits, lanes, want_lp), cache

        def _decode(params, token, cache, lanes, want_lp):
            logits, cache = decode_step(ctx, params, token, cache, cfg)
            return _sample(logits, lanes, want_lp), cache

        def _chunk(params, tokens, cache, row, start, length, lanes,
                   want_lp):
            logits, cache = prefill_chunk(ctx, params, tokens, cfg, cache,
                                          row, start, length)
            return _sample(logits, lanes, want_lp), cache

        self._prefill = jax.jit(_prefill, static_argnums=(5,))
        self._decode = jax.jit(_decode, static_argnums=(4,))
        self._chunk = jax.jit(_chunk, static_argnums=(7,))

        # --- self-speculative closures ---------------------------------
        # draft: the identical lockstep decode graph with the low-rank
        # sliver sliced to rank 0 (Ctx.draft) — Q-only logits, with the
        # drafted tokens' KV persisted at the usual slots through the
        # very same step graph a plain decode uses (the verify chunk is
        # read-only, so accepted slots keep these step-graph entries)
        dctx = dataclasses.replace(ctx, draft=True)

        def _draft(params, token, cache, lanes):
            logits, cache = decode_step(dctx, params, token, cache, cfg)
            tok = sample_tokens(logits[:, -1].astype(jnp.float32), *lanes)
            return tok[:, None], cache

        # the whole k-1 draft chain runs as ONE compiled dispatch:
        # dispatch + host-sync overhead per round stays O(1) in k
        # instead of O(k), which is where the CPU speedup lives and
        # what keeps TPU launch counts flat. The chain is unrolled in
        # the trace rather than lax.scan'd — XLA:CPU serializes loop
        # bodies onto one thread (measured ~30x slower per round) while
        # the unrolled chain keeps intra-op parallelism, and k <=
        # spec_k keeps the trace small. Static k: at most spec_k-1
        # compiled variants (k clamps down only when a lane nears its
        # token budget), all pre-compiled by warmup()
        def _draft_span(params, token, cache, lanes, k):
            toks = []
            for _ in range(k - 1):
                token, cache = _draft(params, token, cache, lanes)
                toks.append(token)
            return jnp.stack(toks), cache  # (k-1, B, 1)

        # verify: one (1, spec_k) chunk re-scores [last token ‖ drafts]
        # with the full Q+LR model; every position is sampled in-graph
        # with the lane's counter-based keys (idx0 + j). Chunk logits
        # only ever gate acceptance (and supply logprobs for tokens the
        # draft already proposed) — emitted tokens all originate in the
        # step-shaped graph, see _spec_round
        # read-only verify when the draft IS the target (no LR params
        # to slice): storage keeps the drafts' bit-exact step-graph
        # K/V and greedy spec output is structurally identical to
        # non-speculative decode. Models with LR slivers need the
        # chunk to upgrade the drafts' Q-only K/V to full-model
        # entries — see verify_chunk for the parity consequences.
        spec_store = _params_have_lowrank(params)

        def _verify(params, tokens, cache, row, start, length, lane,
                    want_lp):
            logits, cache = verify_chunk(ctx, params, tokens, cfg, cache,
                                         row, start, length,
                                         store=spec_store)
            lg = logits[0].astype(jnp.float32)
            kk = lg.shape[0]
            temp, top_p, top_k, seed, idx0 = lane
            tok = sample_tokens(
                lg, jnp.full((kk,), temp, jnp.float32),
                jnp.full((kk,), top_p, jnp.float32),
                jnp.full((kk,), top_k, jnp.int32),
                jnp.full((kk,), seed, jnp.int32),
                idx0 + jnp.arange(kk, dtype=jnp.int32))
            return (tok, _lp(lg, tok, want_lp)), cache

        # rollback: rewrite the verified rows' positions to
        # p + n_accepted (one fused dispatch over every layer's pos
        # leaf; the groups stack broadcasts over its leading axis).
        # Rejected-tail KV needs no page work — its slots live in pages
        # the request already owns (pre-allocated at admission), and
        # the pos predicate masks them dead until overwritten
        def _rewind(cache, mask, newpos):
            def walk(c):
                if isinstance(c, dict):
                    out = {k: walk(v) for k, v in c.items()}
                    if "pos" in c and hasattr(c["pos"], "ndim"):
                        p = c["pos"]
                        m, np_ = ((mask, newpos) if p.ndim == 1
                                  else (mask[None], newpos[None]))
                        out["pos"] = jnp.where(m, np_.astype(p.dtype), p)
                    return out
                if isinstance(c, list):
                    return [walk(v) for v in c]
                if isinstance(c, tuple):
                    return tuple(walk(v) for v in c)
                return c
            return walk(cache)

        self._draft_span = jax.jit(_draft_span, static_argnums=(4,))
        self._verify = jax.jit(_verify, static_argnums=(7,))
        self._rewind = jax.jit(_rewind)

        # --- accuracy-drift probe --------------------------------------
        # one jitted shadow dispatch re-runs this step's decode over the
        # *pre-step* cache twice — under the serving lowering and under a
        # reference lowering of the same quantized params (default
        # fused="off": the dequant-then-matmul ground truth the fused
        # kernels are verified against) — and reduces the final-position
        # logits to per-lane KL(serving ‖ reference), argmax agreement,
        # max-|Δlogit| and a non-finite element count. Both cache outputs
        # are discarded and nothing is donated, so the probe is read-only
        # by construction: served tokens are bit-identical with the
        # monitor on or off.
        if sc.drift_monitor:
            rctx = dataclasses.replace(ctx, fused=sc.drift_ref_fused)
            rctx.use_pallas = fused_mode(rctx) == "kernel"

            def _drift_probe(params, token, cache):
                lg_s, _ = decode_step(ctx, params, token, cache, cfg)
                with jax.named_scope("drift_ref"):
                    lg_r, _ = decode_step(rctx, params, token, cache, cfg)
                s = lg_s[:, -1].astype(jnp.float32)
                r = lg_r[:, -1].astype(jnp.float32)
                logp_s = jax.nn.log_softmax(s)
                logp_r = jax.nn.log_softmax(r)
                kl = jnp.sum(jnp.exp(logp_s) * (logp_s - logp_r), axis=-1)
                agree = jnp.argmax(s, axis=-1) == jnp.argmax(r, axis=-1)
                delta = jnp.max(jnp.abs(s - r), axis=-1)
                bad = (jnp.sum(~jnp.isfinite(s), axis=-1)
                       + jnp.sum(~jnp.isfinite(r), axis=-1))
                return kl, agree, delta, bad

            self._drift_probe = jax.jit(_drift_probe)
            self._drift_every = max(1, round(1.0 / sc.drift_sample_rate))
        else:
            self._drift_probe = None
            self._drift_every = 0
        self._drift_step = 0

        # paged geometry: the chunk width is the (even) prefill length,
        # chunk starts are page-aligned (matched prefixes are whole
        # pages), so int4 nibble pairs always land whole
        self.page_size = sc.page_size + sc.page_size % 2
        if sc.paged:
            # construction-time layout check against the shared kernel
            # constraints — a clear error here instead of a Mosaic
            # lowering failure on the first compiled dispatch. Strict
            # (sublane-tile) floors only bind where the kernels compile
            # for real hardware; interpret-mode CPU runs take any even
            # size.
            validate_page_size(self.page_size,
                               packed=sc.kv_dtype == "int4",
                               strict=jax.default_backend() == "tpu")
        self._chunk_len = self.prefill_len + self.prefill_len % 2 \
            if sc.paged else self.prefill_len
        # compiled chunk widths, widest first: every chunk but a prompt's
        # last runs at _chunk_len (so chunk starts never move); the last
        # runs at the narrowest width that holds what is left of it
        self._chunk_widths = (chunk_ladder(self._chunk_len, self.page_size)
                              if sc.paged else (self._chunk_len,))

        # token-budget config: the unit of prefill work is one compiled-
        # width dispatch, charged at the width dispatched (a partial
        # chunk computes its whole width), and an admission whose
        # prefill completes immediately also joins decode the same step
        # (+1). The construction check below takes the full width: an
        # engine must be able to admit a full-width chunk
        self._step_unit = self._chunk_len if sc.paged else self.prefill_len
        if sc.max_step_tokens is not None:
            if sc.scheduler != "continuous":
                raise ValueError("max_step_tokens needs "
                                 "scheduler='continuous'")
            if sc.max_step_tokens < self._step_unit + 1:
                raise ValueError(
                    f"max_step_tokens={sc.max_step_tokens} cannot cover "
                    f"one prefill dispatch ({self._step_unit} compiled "
                    f"tokens) plus its first decode lane — an idle "
                    f"engine could never admit anything")
        if not 0.0 <= sc.free_watermark < 1.0:
            raise ValueError(f"free_watermark={sc.free_watermark} must "
                             f"be in [0, 1)")
        if sc.max_pages_per_request is not None \
                and sc.max_pages_per_request < 1:
            raise ValueError("max_pages_per_request must be >= 1")
        if (sc.max_pages_per_request is not None
                or sc.free_watermark > 0.0) and not sc.paged:
            raise ValueError("max_pages_per_request / free_watermark "
                             "need ServeConfig(paged=True)")

        # --- continuous-scheduler state ---------------------------------
        self.slots = None                # SlotKVCache | PagedKVCache
        self.sched: Optional[ContinuousScheduler] = None
        self.pool: Optional[PagePool] = None
        self.prefix: Optional[RadixPrefixCache] = None
        self._tok = None
        self._base_seed = sc.seed        # sampling stream base for
        # submit()/step(); generate(seed=) overrides per run
        # per-lane sampling state mirrored into the decode dispatch
        b = sc.decode_batch
        self._lane_temp = np.zeros((b,), np.float32)
        self._lane_top_p = np.ones((b,), np.float32)
        self._lane_top_k = np.zeros((b,), np.int32)
        self._lane_seed = np.zeros((b,), np.int32)
        self._lane_lp = np.zeros((b,), bool)
        self._want_lp = False            # any live lane wants logprobs
        # self-speculative accounting (published unconditionally)
        self._spec_rounds = 0
        self._spec_draft_tokens = 0
        self._spec_accepted_tokens = 0
        self._h_accept = self.registry.histogram(
            "spec_accept_per_round",
            "accepted draft tokens per lane per speculative round")
        # drift-monitor accounting (published unconditionally, like the
        # spec counters: zeros when the monitor is off)
        self._drift_checks = 0
        self._drift_agree = 0
        self._drift_nonfinite = 0
        self._guard_oob = 0
        self._h_drift_kl = self.registry.histogram(
            "drift_kl",
            "per-lane KL(serving ‖ reference) at drift-sampled steps",
            buckets=log_buckets(1e-12, 100.0, 2))
        self._h_drift_delta = self.registry.histogram(
            "drift_logit_delta",
            "per-lane max |Δlogit| vs the reference lowering at "
            "drift-sampled steps",
            buckets=log_buckets(1e-12, 100.0, 2))
        # streaming hook: called as on_token(uid, token, info) for every
        # generated token the moment it is recorded (serve.http fans
        # these out to SSE connections); info is the logprob record when
        # the request asked for logprobs, else None
        self.on_token: Optional[Callable[[int, int, Optional[Dict]],
                                         None]] = None
        self._bucket_stats = SchedulerStats(n_slots=sc.decode_batch)
        if sc.sanitize and sc.scheduler != "continuous":
            raise ValueError("sanitize=True audits the continuous "
                             "engine's slot/page state — it needs "
                             "scheduler='continuous'")
        self._san = Sanitizer() if sc.sanitize else None
        if sc.scheduler == "continuous":
            self._reset_continuous()

    # ------------------------------------------------------------------
    def _reset_continuous(self) -> None:
        sc = self.sc
        self.sched = ContinuousScheduler(sc.decode_batch, sc.eos_id,
                                         sc.max_new_tokens,
                                         max_step_tokens=sc.max_step_tokens)
        self._need_plain = False         # a spec-round rejection forces
        # one step-graph decode (the correction token's source)
        self._tok = jnp.zeros((sc.decode_batch, 1), jnp.int32)
        if not sc.paged:
            self.slots = SlotKVCache(self.cfg, sc.decode_batch, sc.max_len,
                                     sc.kv_dtype)
            return
        ps = self.page_size
        nb = -(-sc.max_len // ps)
        # full residency for every lane + its parked page + one request's
        # worth of prefix-retention headroom
        n_pages = sc.n_pages or (sc.decode_batch * (nb + 1) + nb)
        if n_pages < nb + sc.decode_batch:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one parked page per slot "
                f"plus one full request ({nb} blocks at page_size={ps})")
        self.slots = PagedKVCache(self.cfg, sc.decode_batch, sc.max_len,
                                  sc.kv_dtype, ps, n_pages)
        self.pool = PagePool(n_pages, ps)
        self.prefix = RadixPrefixCache(self.pool) if sc.prefix_cache else None
        # one permanently-allocated private page per slot: retired (and
        # still-prefilling) rows point every unused block-table entry at
        # it, so the decode step's unconditional write never lands in a
        # page another request owns
        self._parked = self.pool.alloc(sc.decode_batch)
        self._row_pages: Dict[int, List[int]] = {}
        self._prefill_jobs: Dict[int, "_PrefillJob"] = {}
        self._prefill_chunks = 0
        self._prefill_tokens_computed = 0
        self._prefill_chunk_tokens_dispatched = 0
        self._prompt_tokens_total = 0
        self._prefix_hit_tokens = 0
        for slot in range(sc.decode_batch):
            self.slots.set_row(slot, [self._parked[slot]] * nb, 0)

    def _req_budget(self, r: Request) -> int:
        """Per-request token budget; ``is not None`` (not truthiness) so
        an explicit max_new_tokens=0 stays 0 — mirror of the scheduler's
        next_admission fix."""
        if r.params is not None and r.params.max_new_tokens is not None:
            return r.params.max_new_tokens
        return (r.max_new_tokens if r.max_new_tokens is not None
                else self.sc.max_new_tokens)

    def _resolve(self, req: Request) -> SamplingParams:
        """Fill a request's ``SamplingParams`` None fields from the
        ServeConfig defaults (and the deprecated ``Request.
        max_new_tokens`` shim) — after this, every field is concrete."""
        sp = req.params or SamplingParams()
        t = (sp.temperature if sp.temperature is not None
             else self.sc.temperature)
        mnt = sp.max_new_tokens
        if mnt is None:
            mnt = req.max_new_tokens
        if mnt is None:
            mnt = self.sc.max_new_tokens
        return dataclasses.replace(sp, temperature=float(t),
                                   max_new_tokens=int(mnt))

    # --- per-lane sampling plumbing -----------------------------------
    def _lanes_for(self, state, idx: int):
        """Single-row lane arrays for a prefill/chunk dispatch sampling
        this request's token number ``idx``."""
        sp = state.sampling
        return (jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([state.seed], jnp.int32),
                jnp.asarray([idx], jnp.int32))

    def _decode_lanes(self):
        """(B,) lane arrays for the lockstep decode dispatch; retired /
        mid-prefill lanes ride greedy (their draw is never read)."""
        idxs = np.zeros((self.sc.decode_batch,), np.int32)
        for s, st in self.sched.table.active.items():
            idxs[s] = len(st.tokens)
        return (jnp.asarray(self._lane_temp), jnp.asarray(self._lane_top_p),
                jnp.asarray(self._lane_top_k), jnp.asarray(self._lane_seed),
                jnp.asarray(idxs))

    def _set_lane(self, slot: int, state) -> None:
        sp = state.sampling
        self._lane_temp[slot] = sp.temperature
        self._lane_top_p[slot] = sp.top_p
        self._lane_top_k[slot] = sp.top_k
        self._lane_seed[slot] = state.seed
        self._lane_lp[slot] = sp.logprobs is not None
        self._want_lp = bool(self._lane_lp.any())

    def _clear_lane(self, slot: int) -> None:
        self._lane_temp[slot] = 0.0
        self._lane_top_p[slot] = 1.0
        self._lane_top_k[slot] = 0
        self._lane_seed[slot] = 0
        self._lane_lp[slot] = False
        self._want_lp = bool(self._lane_lp.any())

    def _lp_entry(self, state, chosen, top_lp,
                  top_ids) -> Optional[Dict]:
        """One request-facing logprob record from host-side values:
        the sampled token's logprob plus the top-n alternatives the
        request asked for (compiled width TOP_LOGPROBS, trimmed here)."""
        n = state.sampling.logprobs
        if n is None:
            return None
        top = [(int(i), float(v))
               for i, v in zip(top_ids[:n], top_lp[:n])]
        return {"logprob": float(chosen), "top_logprobs": top}

    def _record(self, slot: int, token: int, info=None) -> bool:
        """record_token + the streaming on_token fanout."""
        state = self.sched.table.active[slot]
        done = self.sched.record_token(slot, token)
        if self.on_token is not None:
            self.on_token(state.uid, int(token), info)
        return done

    def _validate(self, req: Request) -> None:
        plen = len(req.prompt)
        eff = plen + self._n_vis
        if plen < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.params is not None:
            try:
                req.params.validate()
            except ValueError as e:
                raise ValueError(f"request {req.uid}: {e}") from None
        if self.sc.max_pages_per_request is not None \
                and eff >= self.sc.max_pages_per_request * self.page_size:
            raise ValueError(
                f"request {req.uid}: prompt length {plen} fills the "
                f"max_pages_per_request={self.sc.max_pages_per_request} "
                f"page quota ({self.page_size} slots/page) with no "
                f"decode budget left")
        if eff >= self.sc.max_len:
            raise ValueError(
                f"request {req.uid}: prompt length {plen}"
                + (f" (+{self._n_vis} vision tokens)" if self._n_vis else "")
                + f" leaves no decode budget within max_len={self.sc.max_len}"
                f" — raise ServeConfig.max_len or shorten the prompt")
        if (self.sc.scheduler == "continuous" and not self.sc.paged
                and eff > self.prefill_len):
            # the paged engine has no such cap: chunked prefill feeds any
            # prompt < max_len through the one compiled chunk shape
            raise ValueError(
                f"request {req.uid}: prompt length {plen} exceeds the "
                f"compiled prefill shape prefill_len={self.prefill_len} "
                f"(ServeConfig(paged=True) lifts this via chunked prefill)")

    def _batch_for(self, prompts: np.ndarray) -> Dict[str, jax.Array]:
        b, s = prompts.shape
        batch: Dict[str, jax.Array] = {"tokens": jnp.asarray(prompts)}
        if self.cfg.is_encoder_decoder:
            frames = self.extra.get("frames")
            if frames is None:
                frames = np.zeros(
                    (b, self.cfg.enc_seq, self.cfg.d_frontend), np.float32)
            batch["frames"] = jnp.asarray(frames[:b])
        if self.cfg.n_vision_tokens:
            vis = self.extra.get("vision")
            if vis is None:
                vis = np.zeros((b, self.cfg.n_vision_tokens,
                                self.cfg.d_frontend or self.cfg.d_model),
                               np.float32)
            batch["vision"] = jnp.asarray(vis[:b])
        return batch

    # ==================================================================
    # Streaming API (continuous scheduler)
    # ==================================================================
    def submit(self, req: Request) -> int:
        """Queue a request; it is admitted on the next step() with a free
        slot. Returns the request uid."""
        if self.sc.scheduler != "continuous":
            raise RuntimeError("submit()/step()/drain() need "
                               "ServeConfig(scheduler='continuous')")
        self._validate(req)
        req.params = self._resolve(req)
        req.t_submit = req.t_submit or time.perf_counter()
        self.sched.submit(req)
        self.tel.request_queued(req.uid)
        return req.uid

    # ------------------------------------------------------------------
    # Paged admission: map pages (prefix hits + fresh allocations) into
    # the slot's block table; the prompt then prefills chunk-by-chunk
    # across engine steps (interleaved with decode) instead of in one
    # blocking call.
    # ------------------------------------------------------------------
    def _admit_paged(self, budget: StepBudget) -> Optional[List[Result]]:
        if not self.sched.queue or self.sched.table.n_free == 0:
            return None
        # the admission's first chunk runs this step (prepaid below);
        # cheap gate before touching the prefix tree, at the narrowest
        # width — the exact cost (is the first chunk final, and how
        # wide?) is re-checked after matching
        if not budget.can(self._chunk_widths[-1]):
            self.sched.stats.budget_deferred_admissions += 1
            return None
        nxt = self.sched.next_admission()
        req, state = nxt
        eff = state.prompt_len
        state.budget = min(state.budget, self.sc.max_len - eff)
        ps, nb = self.page_size, self.slots.n_blocks
        if self.sc.max_pages_per_request is not None:
            # page quota: prompt + generation never map more pages than
            # the quota (prompt-only overflow was rejected at submit)
            state.budget = min(state.budget,
                               self.sc.max_pages_per_request * ps - eff)
        matched: List[int] = []
        if self.prefix is not None:
            # cap: at least one prompt token is recomputed — the final
            # chunk's logits seed the first sampled token
            matched = self.prefix.match(req.prompt,
                                        max_blocks=(eff - 1) // ps)
        m_tok = len(matched) * ps
        cost = self._chunk_cost(eff - m_tok)
        need = -(-(eff + max(state.budget, 0)) // ps) - len(matched)
        fresh = self.pool.alloc(need) if budget.can(cost) else None
        if fresh is None:
            # pool pressure (or the exact budget cost no longer fits):
            # roll the match back (refs AND counters, so retries don't
            # inflate hit stats), put the request back at the queue
            # head, retry when a retirement frees pages / budget
            if self.prefix is not None:
                self.prefix.release_match(matched, (eff - 1) // ps)
            self.sched.queue.appendleft(req)
            if not budget.can(cost):
                self.sched.stats.budget_deferred_admissions += 1
            return None
        state.seed = lane_seed(state.sampling.seed, self._base_seed,
                               req.uid)
        budget.take(cost)
        slot = self.sched.admit(state)
        self._set_lane(slot, state)
        self.tel.request_admitted(req.uid)
        row = matched + fresh
        self._row_pages[slot] = row
        self.slots.set_row(slot, row + [self._parked[slot]] * (nb - len(row)),
                           m_tok)
        self._prefill_jobs[slot] = _PrefillJob(req=req, state=state,
                                               next=m_tok,
                                               matched_tokens=m_tok,
                                               prepaid=True)
        self._prompt_tokens_total += eff
        self._prefix_hit_tokens += m_tok
        return []

    def _chunk_width(self, left: int) -> int:
        """Compiled width of the next chunk of a prompt with ``left``
        tokens still to prefill: the full chunk width, or for the final
        chunk the narrowest ladder width that holds it."""
        return next((w for w in reversed(self._chunk_widths) if w >= left),
                    self._chunk_len)

    def _chunk_cost(self, left: int) -> int:
        """Token-budget charge of that chunk: its compiled width, +1
        decode lane when it completes the prompt (the slot joins decode
        this very step)."""
        return self._chunk_width(left) + (left <= self._chunk_len)

    def _advance_prefill(self, slot: int) -> List[Result]:
        """Run one prefill chunk for a mid-admission slot; on the final
        chunk, sample the first token and (maybe) retire."""
        job = self._prefill_jobs[slot]
        eff = job.state.prompt_len
        c = self._chunk_len
        start = job.next
        length = min(c, eff - start)
        w = self._chunk_width(eff - start)
        tokens = np.zeros((1, w), np.int32)
        tokens[0, :length] = job.req.prompt[start:start + length]
        final = start + length >= eff
        want_lp = final and job.state.sampling.logprobs is not None
        t0 = time.perf_counter()
        with self.tel.entry("prefill_chunk", (1, w)):
            # non-final chunks discard the sampled token — the lane
            # arrays still ride along so the compiled shape is uniform
            (tok, lpd), self.slots.cache = self._chunk(
                self.params, jnp.asarray(tokens), self.slots.cache,
                jnp.int32(slot), jnp.int32(start), jnp.int32(length),
                self._lanes_for(job.state, 0), want_lp)
            if final:
                first = int(jax.device_get(tok)[0, 0])
            elif self.tel.sync:
                jax.block_until_ready(tok)
        t1 = time.perf_counter()
        job.state.t_prefill += t1 - t0
        self.tel.request_prefill(job.req.uid, start // c, t0, t1)
        job.next = start + length
        self._prefill_chunks += 1
        self._prefill_tokens_computed += length
        self._prefill_chunk_tokens_dispatched += w
        if not final:
            return []
        del self._prefill_jobs[slot]
        if self.prefix is not None:
            # register the prompt's *full* blocks (a partial tail block
            # will also hold this request's decode tokens — unshareable)
            self.prefix.insert(job.req.prompt,
                               self._row_pages[slot][:eff // self.page_size])
        if job.state.budget <= 0:
            # degenerate max_new_tokens=0 — same semantics as unpaged
            job.state.finish_reason = "length"
            return [self._finish(slot)]
        self._tok = self._tok.at[slot, 0].set(first)
        info = None
        if lpd is not None:
            ch, tl, ti = jax.device_get(lpd)
            info = self._lp_entry(job.state, ch[0], tl[0], ti[0])
        done = self._record(slot, first, info)
        self.tel.request_first_token(job.req.uid)
        if done:
            return [self._finish(slot)]
        return []

    def _admit_one(self, budget: StepBudget) -> Optional[List[Result]]:
        """Prefill the next queued request into a free slot (if any)."""
        if self.sc.paged:
            return self._admit_paged(budget)
        if not self.sched.queue or self.sched.table.n_free == 0:
            return None
        # one compiled-width prefill dispatch + the decode lane the new
        # slot occupies this very step
        if not budget.try_take(self.prefill_len + 1):
            self.sched.stats.budget_deferred_admissions += 1
            return None
        nxt = self.sched.next_admission()
        req, state = nxt
        state.seed = lane_seed(state.sampling.seed, self._base_seed,
                               req.uid)
        self.tel.request_admitted(req.uid)
        eff = state.prompt_len + self._n_vis
        state.budget = min(state.budget, self.sc.max_len - eff)

        prompts = np.zeros((1, self.prefill_len), np.int32)
        prompts[0, :state.prompt_len] = req.prompt
        t0 = time.perf_counter()
        # the pristine zero template goes in; a fresh populated copy comes
        # out (never fed back — that would leak recurrent state between
        # consecutive admissions through this buffer)
        with self.tel.entry("prefill", prompts.shape):
            (first, lpd), pf_cache = self._prefill(
                self.params, self._batch_for(prompts),
                self.slots.prefill_cache, jnp.asarray([eff], jnp.int32),
                self._lanes_for(state, 0),
                state.sampling.logprobs is not None)
            first = int(jax.device_get(first)[0, 0])
        t1 = time.perf_counter()
        self.tel.request_prefill(req.uid, 0, t0, t1)

        slot = self.sched.admit(state)
        self._set_lane(slot, state)
        state.t_prefill = t1 - t0
        if state.budget <= 0:
            # degenerate max_new_tokens=0: the prefill token is dropped so
            # both schedulers agree on "0 new tokens" (bucketed truncates
            # to the budget); the slot frees on the same step
            state.finish_reason = "length"
            return [self._finish(slot)]
        self.slots.admit(pf_cache, slot)
        self._tok = self._tok.at[slot, 0].set(first)
        info = None
        if lpd is not None:
            ch, tl, ti = jax.device_get(lpd)
            info = self._lp_entry(state, ch[0], tl[0], ti[0])
        done = self._record(slot, first, info)
        self.tel.request_first_token(req.uid)
        if done:
            return [self._finish(slot)]
        return []

    def _finish(self, slot: int) -> Result:
        state = self.sched.retire(slot)
        self._clear_lane(slot)
        if self.sc.paged:
            # release the slot's pages (tree-registered prompt blocks go
            # cold/retained; private blocks free) and park the row so
            # the lockstep decode write stays harmless
            self.pool.decref(self._row_pages.pop(slot, []))
            self.slots.set_row(
                slot, [self._parked[slot]] * self.slots.n_blocks, 0)
        now = time.perf_counter()
        toks = np.asarray(state.tokens, np.int32)
        # None (not 0.0) when the event never happened: a request that
        # retired without decoding must not look like it decoded
        # instantly, and a missing submit stamp must not fake latency
        ft = state.t_first_token or None
        decode_s = now - ft if ft else None
        ttft_s = ft - state.t_submit if ft and state.t_submit else None
        latency_s = now - state.t_submit if state.t_submit else None
        self.tel.request_retired(state.uid, len(toks), ttft_s, latency_s,
                                 decode_s)
        return Result(
            uid=state.uid, tokens=toks,
            prefill_s=getattr(state, "t_prefill", 0.0) or None,
            decode_s=decode_s, ttft_s=ttft_s, latency_s=latency_s,
            finish_reason=state.finish_reason)

    def abort(self, uid: int) -> Optional[Result]:
        """Cancel a request anywhere in its lifecycle and free its
        resources immediately. Queued: removed before admission.
        Mid-chunked-prefill: the ``_PrefillJob`` is dropped and the
        slot's pages decref'd — prefix-matched pages lose the reference
        the match took, fresh pages free — so a cancel before the first
        token never leaks a refcount. Decoding: the slot retires as if
        the request finished, with the tokens generated so far. Returns
        the (partial) :class:`Result` with ``finish_reason="abort"``, or
        ``None`` when the uid is unknown (already finished or never
        submitted)."""
        if self.sc.scheduler != "continuous":
            raise RuntimeError("abort() needs scheduler='continuous'")
        for i, req in enumerate(self.sched.queue):
            if req.uid == uid:
                del self.sched.queue[i]
                self.sched.stats.aborted += 1
                self.tel.request_retired(uid, 0, None, None, None)
                return Result(uid=uid, tokens=np.zeros((0,), np.int32),
                              finish_reason="abort")
        for slot, state in list(self.sched.table.active.items()):
            if state.uid == uid:
                if self.sc.paged:
                    # a mid-prefill cancel: the job dies here; _finish
                    # releases the mapped pages and re-parks the row
                    self._prefill_jobs.pop(slot, None)
                self.sched.stats.aborted += 1
                state.finish_reason = "abort"
                return self._finish(slot)
        return None

    def step(self) -> List[Result]:
        """Open this step's token-budget ledger, admit queued requests
        while budget and slots allow, advance in-flight chunked prefills
        (paged; oldest-admitted first, each chunk charged against the
        budget), then run one decode step over the decoding slots, all
        inside the ``serve/step`` profiler span. Returns requests
        finished now."""
        if self.sc.scheduler != "continuous":
            raise RuntimeError("step() needs scheduler='continuous'")
        self._steps += 1
        with self.tel.step(self._steps) as span:
            finished, span.decoding = self._step()
        self._sanitize()
        return finished

    def _step(self) -> Tuple[List[Result], int]:
        """The phases of one step; returns the requests finished and the
        number of lanes decoded."""
        tel = self.tel
        finished: List[Result] = []
        with tel.phase("budget"):
            # charge the lanes already decoding (active minus mid-
            # prefill) — they run regardless; admissions/chunks below
            # compete for what's left
            n_jobs = len(self._prefill_jobs) if self.sc.paged else 0
            budget = self.sched.begin_step(
                self.sched.table.n_active - n_jobs)
            if self.sc.paged and self.sc.free_watermark > 0.0:
                self.pool.ensure_free(
                    int(self.sc.free_watermark * self.pool.n_pages))
        with tel.phase("admission"):
            while True:
                done = self._admit_one(budget)
                if done is None:
                    break
                finished.extend(done)

        if self.sc.paged:
            # one chunk per prefilling slot per step — oldest admission
            # first, so FIFO order also bounds prefill wait — with each
            # dispatch charged at the width it is dispatched at (+1 when
            # the final chunk promotes the slot to decode this step);
            # jobs the budget cannot cover resume on a later step
            with tel.phase("prefill"):
                jobs = sorted(self._prefill_jobs.items(),
                              key=lambda kv: kv[1].state.t_admit)
                for slot, job in jobs:
                    if job.prepaid:
                        job.prepaid = False
                    else:
                        cost = self._chunk_cost(job.state.prompt_len
                                                - job.next)
                        if not budget.try_take(cost):
                            self.sched.stats.budget_capped_chunks += 1
                            continue
                    finished.extend(self._advance_prefill(slot))
            decoding = [s for s in self.sched.table.active_slots()
                        if s not in self._prefill_jobs]
        else:
            decoding = self.sched.table.active_slots()
        if not decoding:
            return finished, 0

        context = self._context_tokens(decoding)
        k_round = (self._spec_k_for(decoding, budget)
                   if self.sc.speculative else 0)
        if k_round:
            finished.extend(self._spec_round(decoding, k_round))
            self.sched.note_decode_step(len(decoding), context)
            return finished, len(decoding)

        # drift monitor: keep references to the *pre-step* token/cache —
        # the decode jit is functional (nothing donated), so they stay
        # valid for the shadow probe dispatched after the transfer
        drift_in = ((self._tok, self.slots.cache)
                    if self._drift_due() else None)
        with tel.phase("decode"), tel.entry("decode", self._tok.shape):
            (self._tok, lpd), self.slots.cache = self._decode(
                self.params, self._tok, self.slots.cache,
                self._decode_lanes(), self._want_lp)
            if tel.sync:
                # fence: device time stays in this phase instead of
                # hiding inside the next host transfer
                jax.block_until_ready(self._tok)
        self.sched.note_decode_step(len(decoding), context)
        with tel.phase("transfer"):
            toks = np.asarray(jax.device_get(self._tok))[:, 0]
            lp_host = jax.device_get(lpd) if lpd is not None else None
        if drift_in is not None:
            self._observe_drift(drift_in[0], drift_in[1], decoding)
        with tel.phase("emit"):
            self._host_guard(toks, decoding)
            for slot in decoding:
                info = None
                if lp_host is not None:
                    info = self._lp_entry(self.sched.table.active[slot],
                                          lp_host[0][slot], lp_host[1][slot],
                                          lp_host[2][slot])
                if self._record(slot, toks[slot], info):
                    finished.append(self._finish(slot))
        return finished, len(decoding)

    def _context_tokens(self, decoding: List[int]) -> int:
        """Tokens the decoding lanes attend over in this step, summed:
        each lane's prompt (and vision prefix) plus its tokens so far."""
        active = self.sched.table.active
        return sum(active[s].prompt_len + self._n_vis + len(active[s].tokens)
                   for s in decoding)

    def _sanitize(self) -> None:
        """Post-step invariant audit (``ServeConfig(sanitize=True)``):
        raises :class:`~repro.serve.sanitizer.SanitizerError` when the
        host bookkeeping and device state disagree. Read-only — a
        sanitized engine emits exactly the tokens a bare one does."""
        if self._san is not None:
            self._san.check(self)

    # ------------------------------------------------------------------
    # Accuracy-drift monitor (ServeConfig(drift_monitor=True))
    # ------------------------------------------------------------------
    def _drift_due(self) -> bool:
        """Deterministic sampling cadence over plain decode steps: the
        decision depends only on the step counter, never on tokens, so a
        monitored run replays identically."""
        if self._drift_probe is None:
            return False
        due = self._drift_step % self._drift_every == 0
        self._drift_step += 1
        return due

    def _observe_drift(self, token, cache, decoding: List[int]) -> None:
        """Shadow-compare this step's serving logits against the
        reference lowering and fold the per-lane divergences into the
        registry. Read-only: the probe's cache outputs are discarded."""
        out = self._drift_probe(self.params, token, cache)
        with jax.named_scope("drift_probe"):
            # fence: the probe's sync is the sampled monitoring cost, not
            # part of the serving step's transfer budget
            kl, agree, delta, bad = map(np.asarray, jax.device_get(out))
        for slot in decoding:
            self._drift_checks += 1
            self._drift_agree += int(agree[slot])
            self._drift_nonfinite += int(bad[slot])
            if np.isfinite(kl[slot]):
                # tiny negative KL is float32 round-off, clamp to the
                # histogram's domain
                self._h_drift_kl.observe(max(float(kl[slot]), 0.0))
            if np.isfinite(delta[slot]):
                self._h_drift_delta.observe(float(delta[slot]))

    def _host_guard(self, toks: np.ndarray, decoding: List[int]) -> None:
        """Always-cheap sanity counter over the tokens just sampled: a
        token outside [0, vocab) means the logits went bad upstream
        (NaN/inf collapse the in-graph sample to lane garbage). Pure
        host arithmetic on an already-transferred array."""
        t = toks[decoding]
        self._guard_oob += int(np.sum((t < 0) | (t >= self.cfg.vocab)))

    # ------------------------------------------------------------------
    # Self-speculative decoding: Q-only draft, full Q+LR verify
    # ------------------------------------------------------------------
    def _spec_k_for(self, decoding: List[int],
                    budget: StepBudget) -> int:
        """Speculative-round eligibility, returning the window width k
        (0 = run plain per-token decode this step). Requires every
        decoding lane greedy — temperature lanes fall back to per-token
        decode, whose counter-based draws are per-token by construction
        — no pending post-rejection correction (``_need_plain``),
        enough per-lane budget for the up-to-(k-1) emitted drafts, and
        step-budget headroom for the extra compiled dispatches: (k-1)
        draft dispatches over n lanes plus n verify chunks of width k,
        beyond the one decode already charged at begin_step."""
        if self._need_plain:
            self._need_plain = False
            return 0
        active = self.sched.table.active
        k = self.sc.spec_k
        for s in decoding:
            st = active[s]
            if st.sampling.temperature > 0.0:
                return 0
            # a round emits at most k-1 tokens for this lane
            k = min(k, st.budget - len(st.tokens) + 1)
        if k < 2:
            return 0
        n = len(decoding)
        if not budget.try_take((k - 1) * n + k * n):
            return 0
        return k

    def _verify_lane(self, state):
        """Scalar lane tuple for one verify chunk: the request's
        sampling controls plus its next token index (position j of the
        chunk samples with counter key idx0 + j)."""
        sp = state.sampling
        return (jnp.float32(sp.temperature), jnp.float32(sp.top_p),
                jnp.int32(sp.top_k), jnp.int32(state.seed),
                jnp.int32(len(state.tokens)))

    def _spec_round(self, decoding: List[int], k: int) -> List[Result]:
        """One self-speculative round over the (all-greedy) decoding
        lanes: k-1 Q-only draft steps chain through the lockstep decode
        graph, then one full-model verify chunk per lane re-scores
        [last token ‖ drafts] — read-only over the KV storage, see
        :func:`verify_chunk` — and the longest draft prefix matching
        the verify model's predictions is accepted. Only those accepted
        drafts are emitted: the verify model's own next token (the
        classic correction/bonus token) is deliberately NOT taken from
        the chunk. A chunk computes attention with a different float
        reduction order than the per-token decode graph, so its argmax
        can flip on near-tied logits — emitting it would make spec
        output diverge from non-speculative decode on exactly those
        ties. Instead the round marks the engine for one plain decode
        step (``_need_plain``) whenever any lane rejected, and the
        correction token comes out of the step graph itself; a fully
        accepting lane just lets the next round's verify position 0
        re-score what would have been its bonus token. Greedy spec
        output is therefore token-identical to non-speculative decode
        by construction, not by numerical luck. Positions rewind to
        p + n_emitted; rejected-tail KV lives in pages the request
        already owns (pre-allocated at admission), so no page alloc or
        decref happens inside a round — refcounts are conserved by
        construction and the stale tail is masked dead by the pos
        predicate until the next write lands there."""
        tel = self.tel
        sc = self.sc
        active = self.sched.table.active
        states = {s: active[s] for s in decoding}
        # next-write slot per lane: pos = prompt(+vision) + generated - 1
        p0 = {s: states[s].prompt_len + self._n_vis
              + len(states[s].tokens) - 1 for s in decoding}
        lanes = self._decode_lanes()
        with tel.phase("decode"), \
                tel.entry("draft", (k - 1,) + tuple(self._tok.shape)):
            drafts, self.slots.cache = self._draft_span(
                self.params, self._tok, self.slots.cache, lanes, k)
        results: List[Result] = []
        b = sc.decode_batch
        mask = np.zeros((b,), bool)
        newpos = np.zeros((b,), np.int32)
        n_accepted = 0
        with tel.phase("verify"):
            # (k-1, B, 1) scan stack → (B, k-1) host table
            draft_host = np.asarray(jax.device_get(drafts))[:, :, 0].T
            verify = {}
            for s in decoding:
                st = states[s]
                fed = np.zeros((1, sc.spec_k), np.int32)
                fed[0, 0] = st.tokens[-1]
                fed[0, 1:k] = draft_host[s, :k - 1]
                with tel.entry("verify", (1, sc.spec_k)):
                    verify[s], self.slots.cache = self._verify(
                        self.params, jnp.asarray(fed), self.slots.cache,
                        jnp.int32(s), jnp.int32(p0[s]), jnp.int32(k),
                        self._verify_lane(st),
                        st.sampling.logprobs is not None)
        with tel.phase("transfer"):
            tok_host = np.asarray(jax.device_get(self._tok)).copy()
            hosted = {s: (np.asarray(jax.device_get(tv)),
                          jax.device_get(lpd) if lpd is not None else None)
                      for s, (tv, lpd) in verify.items()}
        with tel.phase("emit"):
            for s in decoding:
                st = states[s]
                tgt, lp_host = hosted[s]
                # acceptance: draft j survives while it matches the verify
                # model's prediction at the same position — the greedy-
                # speculative rule. An accepted draft IS the verify token
                # (they compared equal), so emitting tgt[j] below emits the
                # draft, with the chunk's logprob row for that position.
                n_acc = 1
                while (n_acc < k
                       and draft_host[s, n_acc - 1] == tgt[n_acc - 1]):
                    n_acc += 1
                n_accepted += n_acc - 1
                self._h_accept.observe(n_acc - 1)
                if n_acc < k:
                    # a rejected draft would be re-proposed verbatim next
                    # round (drafting is deterministic); the correction
                    # must come from a plain step-graph decode
                    self._need_plain = True
                rec = 0
                done = False
                for j in range(n_acc - 1):
                    info = None
                    if lp_host is not None:
                        info = self._lp_entry(st, lp_host[0][j],
                                              lp_host[1][j], lp_host[2][j])
                    rec += 1
                    # a stop token inside the accepted window truncates
                    # here — tokens past it are never recorded, matching
                    # non-speculative retirement exactly
                    if self._record(s, int(tgt[j]), info):
                        done = True
                        break
                if rec:
                    tok_host[s, 0] = int(tgt[rec - 1])
                if done:
                    # _finish re-parks the row at pos 0 — keep the lane out
                    # of the rewind so the reset sticks instead of being
                    # overwritten with the stale frontier
                    results.append(self._finish(s))
                else:
                    mask[s] = True
                    newpos[s] = p0[s] + rec
        with tel.phase("verify"):
            self._tok = jnp.asarray(tok_host)
            self.slots.cache = self._rewind(
                self.slots.cache, jnp.asarray(mask), jnp.asarray(newpos))
        self._spec_rounds += 1
        self._spec_draft_tokens += (k - 1) * len(decoding)
        self._spec_accepted_tokens += n_accepted
        return results

    def drain(self) -> List[Result]:
        """Run step() until queue and slots are empty; results by uid."""
        if self.sc.scheduler != "continuous":
            raise RuntimeError("drain() needs scheduler='continuous'")
        results: List[Result] = []
        while self.sched.has_work:
            results.extend(self.step())
        results.sort(key=lambda r: r.uid)
        return results

    def _collect(self) -> MetricsRegistry:
        """Publish every live component's series into the registry and
        return it — the single collection path behind ``stats()``,
        ``metrics()``, and ``prometheus()``. Both scheduler modes emit
        the same common key set (bucketed counts admissions/retirements
        too), so downstream consumers never branch on scheduler type;
        the paged engine adds page-pool, prefix-cache, and
        chunked-prefill work accounting, and an enabled telemetry
        recorder adds latency/phase histograms + compile tracking."""
        reg = self.registry
        s = (self._bucket_stats if self.sc.scheduler == "bucketed"
             else self.sched.stats)
        s.publish(reg)
        if self.sc.paged:
            self.pool.publish(reg)
            if self.prefix is not None:
                self.prefix.publish(reg)
            hit = self._prefix_hit_tokens
            total = self._prompt_tokens_total
            reg.counter("prefill_chunks", "chunked-prefill dispatches"
                        ).set(self._prefill_chunks)
            reg.counter("prefill_tokens_computed",
                        "prompt tokens actually prefilled"
                        ).set(self._prefill_tokens_computed)
            reg.counter("prefill_chunk_tokens_dispatched",
                        "compiled widths of the prefill chunks "
                        "dispatched, summed (pad share: 1 - "
                        "prefill_tokens_computed / this)"
                        ).set(self._prefill_chunk_tokens_dispatched)
            reg.counter("prompt_tokens_total", "prompt tokens submitted"
                        ).set(total)
            reg.counter("prefix_hit_tokens",
                        "prompt tokens served from the prefix cache"
                        ).set(hit)
            reg.gauge("prefix_hit_rate", "prefix_hit_tokens / "
                      "prompt_tokens_total"
                      ).set(round(hit / total, 4) if total else 0.0)
        # speculative counters are part of the uniform key set (zeros
        # when the mode is off) so dashboards never branch on config
        reg.counter("spec_rounds", "self-speculative rounds executed"
                    ).set(self._spec_rounds)
        reg.counter("spec_draft_tokens", "Q-only draft tokens proposed"
                    ).set(self._spec_draft_tokens)
        reg.counter("spec_accepted_tokens",
                    "draft tokens accepted by the Q+LR verify"
                    ).set(self._spec_accepted_tokens)
        reg.gauge("spec_acceptance_rate",
                  "spec_accepted_tokens / spec_draft_tokens").set(
            round(self._spec_accepted_tokens / self._spec_draft_tokens, 4)
            if self._spec_draft_tokens else 0.0)
        # drift-monitor counters follow the same uniform-key-set rule
        reg.counter("drift_checks",
                    "per-lane shadow comparisons executed"
                    ).set(self._drift_checks)
        reg.counter("drift_top1_agree",
                    "shadow comparisons whose argmax matched the "
                    "reference lowering").set(self._drift_agree)
        reg.counter("drift_nonfinite",
                    "non-finite logit elements seen by the drift probe"
                    ).set(self._drift_nonfinite)
        reg.counter("guard_token_oob",
                    "sampled tokens outside [0, vocab) — upstream "
                    "logit corruption").set(self._guard_oob)
        reg.gauge("drift_top1_agreement_rate",
                  "drift_top1_agree / drift_checks").set(
            round(self._drift_agree / self._drift_checks, 4)
            if self._drift_checks else 1.0)
        # Python's cycle collector, process-wide (every engine and
        # thread), since the last reset_stats()
        n, gen2, pause = (a - b for a, b in zip(self._gc.totals(),
                                                 self._gc_base))
        reg.counter("gc_collections",
                    "cycle-collector passes, process-wide").set(n)
        reg.counter("gc_collections_gen2",
                    "full (generation 2) collector passes").set(gen2)
        reg.counter("gc_pause_seconds",
                    "seconds inside collector passes").set(pause)
        self.tel.publish()
        return reg

    def stats(self) -> Dict[str, float]:
        """One uniform registry snapshot across scheduler modes —
        legacy keys preserved (``admitted``/``retired``/``eos_retired``
        /``decode_steps``/``occupancy`` everywhere; page-pool, prefix
        and chunk accounting under the paged engine; telemetry
        histograms as nested summaries when enabled)."""
        return self._collect().snapshot()

    # ``metrics()`` is the serving-convention alias
    metrics = stats

    def prometheus(self) -> str:
        """Prometheus text exposition of the same registry snapshot."""
        return self._collect().prometheus()

    def write_trace(self, path: str, jsonl_path: Optional[str] = None) -> str:
        """Export the Chrome trace-event JSON (Perfetto-loadable); with
        ``jsonl_path``, also the flat JSONL event stream. Needs
        ``ServeConfig(telemetry=True)``."""
        if not self.tel.enabled:
            raise RuntimeError("trace export needs ServeConfig("
                               "telemetry=True)")
        out = self.tel.tracer.write_chrome(path)
        if jsonl_path:
            self.tel.tracer.write_jsonl(jsonl_path)
        return out

    def reset_stats(self) -> None:
        """Start a fresh measurement window — histograms, counters,
        pool/prefix stats, trace — without touching scheduler state or
        compiled shapes. ``generate()`` calls this implicitly; callers
        driving ``submit()``/``step()`` directly (benchmarks timing
        repeated runs on one warmed engine) call it between runs."""
        self._reset_stats()

    def _reset_stats(self) -> None:
        if self.sched is not None:
            self.sched.stats = type(self.sched.stats)(
                n_slots=self.sc.decode_batch)
        self._bucket_stats = SchedulerStats(n_slots=self.sc.decode_batch)
        if self.sc.paged:
            self.pool.reset_stats()
            if self.prefix is not None:
                self.prefix.reset_stats()
            self._prefill_chunks = 0
            self._prefill_tokens_computed = 0
            self._prefill_chunk_tokens_dispatched = 0
            self._prompt_tokens_total = 0
            self._prefix_hit_tokens = 0
        self._spec_rounds = 0
        self._spec_draft_tokens = 0
        self._spec_accepted_tokens = 0
        self._drift_checks = 0
        self._drift_agree = 0
        self._drift_nonfinite = 0
        self._guard_oob = 0
        self._gc_base = self._gc.totals()
        # histogram samples reset even with telemetry off — the
        # acceptance histogram is registry-resident either way
        self.registry.reset_histograms()
        # fresh trace + histograms per measured run (compile accounting
        # survives — it describes the engine session)
        self.tel.reset_run()

    def warmup(self) -> None:
        """Trigger the compiles (prefill, or every width of the paged
        chunk ladder, + decode; + draft/verify/rewind under speculative
        mode — the dummy's budget covers one full-k round) with a dummy
        request so steady-state timing excludes compilation. Counters
        are reset afterwards — the dummy never shows in stats()."""
        if self.sc.scheduler != "continuous":
            return
        # speculative: spec_k + 1 covers one full-k round plus a
        # clamped k=2 round for the leftover token
        mnt = self.sc.spec_k + 1 if self.sc.speculative else 2
        dummy = Request(uid=-1, prompt=np.zeros((1,), np.int32),
                        max_new_tokens=mnt)
        self.submit(dummy)
        while self.sched.has_work:
            self.step()
        if self.sc.paged:
            # the dummy's one-token prompt ran the narrowest chunk width;
            # run every wider one on the idle state (jit is pure: the
            # result is dropped), so no admission waits on a compile
            lanes = (jnp.zeros((1,), jnp.float32),
                     jnp.ones((1,), jnp.float32),
                     jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                     jnp.zeros((1,), jnp.int32))
            for w in self._chunk_widths[:-1]:
                with self.tel.entry("prefill_chunk", (1, w)):
                    jax.block_until_ready(self._chunk(
                        self.params, jnp.zeros((1, w), jnp.int32),
                        self.slots.cache, jnp.int32(0), jnp.int32(0),
                        jnp.int32(1), lanes, False)[0][0])
        if self.sc.speculative:
            # the dummy run only exercises k = spec_k; the clamped
            # variants (a lane close to its token budget shrinks the
            # round) would otherwise compile mid-serve, which a short
            # benchmark reads as a 100x throughput cliff. jit is pure:
            # call each variant on the idle state and drop the result
            lanes = self._decode_lanes()
            for kk in range(2, self.sc.spec_k + 1):
                jax.block_until_ready(self._draft_span(
                    self.params, self._tok, self.slots.cache, lanes, kk)[0])
            # post-rejection correction tokens come from the plain
            # decode path, which a fully-accepting dummy run never
            # touches — compile it here so the first rejection
            # mid-serve doesn't stall on a compile
            jax.block_until_ready(self._decode(
                self.params, self._tok, self.slots.cache, lanes,
                False)[0][0])
        self._reset_stats()

    # ==================================================================
    # Bucketed baseline (dry-run-grade scheduler)
    # ==================================================================
    def _bucket_lanes(self, reqs: List[Request], seeds: List[int],
                      idx: int):
        """(B,) lane arrays for one bucket dispatch at token ``idx`` —
        same counter-based streams as the continuous engine, so the two
        schedulers agree token-for-token per request. Padding lanes
        ride greedy."""
        b = self.sc.decode_batch
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        sds = np.zeros((b,), np.int32)
        for i, r in enumerate(reqs):
            temps[i] = r.params.temperature
            top_ps[i] = r.params.top_p
            top_ks[i] = r.params.top_k
            sds[i] = seeds[i]
        return (jnp.asarray(temps), jnp.asarray(top_ps),
                jnp.asarray(top_ks), jnp.asarray(sds),
                jnp.full((b,), idx, jnp.int32))

    def _run_bucket(self, reqs: List[Request],
                    base_seed: int) -> List[Result]:
        sc = self.sc
        b = sc.decode_batch
        plen = len(reqs[0].prompt)
        assert all(len(r.prompt) == plen for r in reqs)
        prompts = np.zeros((b, plen), np.int32)
        stops: List[frozenset] = []
        seeds: List[int] = []
        for i, r in enumerate(reqs):
            prompts[i] = r.prompt
            st = frozenset(r.params.stop)
            if sc.eos_id >= 0:
                st = st | {sc.eos_id}
            stops.append(st)
            seeds.append(lane_seed(r.params.seed, base_seed, r.uid))

        t0 = time.perf_counter()
        cache = self._init_cache()
        # first token goes through the same per-lane sampling path as
        # decode (token index 0, like the continuous engine's prefill)
        (tok, _), cache = self._prefill(self.params,
                                        self._batch_for(prompts),
                                        cache, None,
                                        self._bucket_lanes(reqs, seeds, 0),
                                        False)
        jax.block_until_ready(tok)
        t1 = time.perf_counter()

        budget = max(self._req_budget(r) for r in reqs)
        budget = min(budget, sc.max_len - plen - self._n_vis)
        out = np.zeros((b, budget), np.int32)
        done = np.zeros((b,), bool)
        n = 0
        for step in range(budget):
            out[:, step] = np.asarray(tok[:, 0])
            for i in range(len(reqs)):
                done[i] |= int(out[i, step]) in stops[i]
            n = step + 1
            if done[:len(reqs)].all():
                break
            # a lane is useful only while its (real) request still needs
            # tokens — padding rows and early-stop rows ride along wasted
            useful = sum(1 for i, r in enumerate(reqs)
                         if not done[i] and step < self._req_budget(r))
            self._bucket_stats.decode_steps += 1
            self._bucket_stats.decode_slot_steps += useful
            # each lane attends over the prompt and the step + 1 tokens
            # sampled so far
            self._bucket_stats.decode_context_tokens += useful * (
                plen + self._n_vis + step + 1)
            # token index step+1: out[:, step] was token `step`
            (tok, _), cache = self._decode(
                self.params, tok, cache,
                self._bucket_lanes(reqs, seeds, step + 1), False)
        jax.block_until_ready(tok)
        t2 = time.perf_counter()

        results = []
        self._bucket_stats.admitted += len(reqs)
        self._bucket_stats.retired += len(reqs)
        for i, r in enumerate(reqs):
            toks = out[i, :n]
            # stop truncation first (stop wins over budget on the same
            # token — continuous semantics), then the per-request budget
            cut = next((j for j in range(len(toks))
                        if int(toks[j]) in stops[i]), None)
            if cut is not None:
                toks = toks[:cut + 1]
            lim = self._req_budget(r)
            lim = min(lim, sc.max_len - plen - self._n_vis)
            toks = toks[:lim]
            stopped = (cut is not None and cut < lim)
            finish = "stop" if stopped else "length"
            if stopped and sc.eos_id >= 0 and toks[-1] == sc.eos_id:
                self._bucket_stats.eos_retired += 1
            since = r.t_submit or t0     # queue wait counts toward latency
            results.append(Result(uid=r.uid, tokens=toks,
                                  prefill_s=t1 - t0, decode_s=t2 - t1,
                                  ttft_s=t1 - since,
                                  latency_s=t2 - since,
                                  finish_reason=finish))
        return results

    def _generate_bucketed(self, requests: Sequence[Request],
                           seed: int) -> List[Result]:
        buckets: Dict[int, List[Request]] = {}
        for r in requests:
            buckets.setdefault(len(r.prompt), []).append(r)
        results: List[Result] = []
        for plen in sorted(buckets):
            queue = buckets[plen]
            for i in range(0, len(queue), self.sc.decode_batch):
                results.extend(
                    self._run_bucket(queue[i: i + self.sc.decode_batch],
                                     seed))
        results.sort(key=lambda r: r.uid)
        return results

    # ==================================================================
    def generate(self, requests: Sequence[Request],
                 seed: int = 0) -> List[Result]:
        """Run all requests through the configured scheduler. Each call
        is a fresh run: sampling stream re-seeded, stats() reset, and
        submission timestamps re-stamped (so reusing Request objects
        across runs cannot inflate latency metrics)."""
        now = time.perf_counter()
        for r in requests:
            self._validate(r)
            r.params = self._resolve(r)
            r.t_submit = now
        self._reset_stats()
        if self.sc.scheduler == "bucketed":
            return self._generate_bucketed(requests, seed)
        self._base_seed = seed
        for r in requests:
            self.submit(r)
        out = self.drain()
        self.tel.stop_profiler()     # a short run may never hit the
        # profile_steps threshold; don't leave the capture open
        return out
