"""PTQ → serve: quantize a whole model with SRR and serve it continuously.

    PYTHONPATH=src python examples/ptq_serve.py [--arch minitron-4b]

The paper's deployment scenario: calibrate on a handful of batches,
decompose every projection into Q + LR (per-matrix k*), then serve
requests through the continuous-batching engine — int8 KV cache on,
requests streamed in via ``submit()``/``step()`` so late arrivals join
mid-flight — and compare against the w-only and QER baselines.

A final act serves the production traffic shape: many requests sharing
one system prompt through the **paged** engine (``--arch`` permitting —
paged needs a pure-attention stack, so this step runs on phi3-mini),
where the radix-tree prefix cache maps the shared blocks into each new
request's block table and the printed prefix-hit rate shows how much
prefill the cache deleted. The paged act runs with telemetry enabled,
so it also prints the step-phase p50 breakdown (budget / admission /
prefill / decode / transfer) straight from the engine's metrics
registry. The act also arms the token-budget step scheduler
(``max_step_tokens``), bounding per-step prefill + decode work.

The last act turns on **self-speculative decoding**: the quantized base
`Q` alone drafts tokens (the low-rank sliver is skipped — a free draft
model living inside the serving weights) and the full `Q + LR` model
verifies k at a time in one chunked dispatch. It prints the measured
acceptance rate and tok/s next to the plain per-token engine. For a
real Q+LR model both numbers hinge on how closely the quantized base
tracks the corrected model — the act reports that trade-off honestly
rather than a synthetic best case (``benchmarks/serve_spec.py``
measures the mechanism at its acceptance ceiling).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.api import PTQConfig
from repro.data import capture_calibration, data_config_for
from repro.models import Ctx, init_lm, lm_loss
from repro.models.quantize import quantize_model_params
from repro.quant.base import QuantizerConfig
from repro.serve import Engine, Request, SamplingParams, ServeConfig


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="minitron-4b")
    p.add_argument("--rank", type=int, default=16)
    args = p.parse_args()

    cfg = get_config(args.arch).reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    dcfg = data_config_for(cfg, seq_len=32, global_batch=4)

    print("[1/5] calibrating …")
    stats = capture_calibration(
        params, cfg, dcfg, lambda c, pp, b, cc: lm_loss(c, pp, b, cc),
        n_batches=2)

    print("[2/5] quantizing (3-bit MXINT + SRR rank allocation) …")
    results = {}
    for method in ("w-only", "qer", "srr"):
        ptq = PTQConfig(method=method,
                        scaling="identity" if method == "w-only"
                        else "qera-exact",
                        rank=args.rank,
                        quantizer=QuantizerConfig("mxint", 3, 32))
        t0 = time.perf_counter()
        qp, reports = quantize_model_params(params, stats, ptq)
        dt = time.perf_counter() - t0
        from repro.data import host_batch
        loss = float(lm_loss(Ctx(), qp, host_batch(dcfg, 999), cfg))
        kbar = sum(r.k_star for r in reports) / max(len(reports), 1)
        results[method] = qp
        print(f"   {method:7s}: eval loss {loss:.4f}  mean k*={kbar:4.1f}  "
              f"({dt:.1f}s)")

    print("[3/5] serving the SRR model (continuous batching, int8 KV) …")
    eng = Engine(results["srr"], cfg,
                 ServeConfig(max_len=96, decode_batch=4, max_new_tokens=12,
                             kv_dtype="int8", scheduler="continuous",
                             prefill_len=16 + (cfg.n_vision_tokens or 0)))
    rng = np.random.default_rng(0)
    # stream requests in: 4 up front, 4 more arriving mid-decode — and
    # mix per-request sampling in the same batch (greedy lanes decode
    # next to temperature/top-p lanes, each with its own PRNG stream)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, size=int(rng.integers(6, 14))).astype(np.int32),
        params=SamplingParams(
            max_new_tokens=int(rng.integers(6, 13)),
            temperature=0.0 if i % 2 == 0 else 0.8,
            top_p=1.0 if i % 2 == 0 else 0.9,
            seed=i)) for i in range(8)]
    out = []
    for r in reqs[:4]:
        eng.submit(r)
    for _ in range(4):                       # a few steps before the rest
        out.extend(eng.step())
    for r in reqs[4:]:                       # late arrivals join mid-flight
        eng.submit(r)
    out.extend(eng.drain())
    out.sort(key=lambda r: r.uid)
    for r in out[:3]:
        kind = "greedy" if r.uid % 2 == 0 else "sampled"
        print(f"   req {r.uid} ({kind}, {r.finish_reason}): "
              f"{r.tokens.tolist()}")
    toks = sum(len(r.tokens) for r in out)
    st = eng.stats()
    print(f"   {len(out)} requests, {toks} new tokens, "
          f"lane occupancy {st['occupancy']:.2f}")

    print("[4/5] paged serving: one system prompt, many users "
          "(prefix-cache reuse) …")
    # paged needs a pure-attention stack; run this act on phi3-mini if
    # the requested arch doesn't qualify
    pcfg, pparams = cfg, results["srr"]
    if set(pcfg.block_pattern) != {"attn"} or pcfg.attn_kind == "mla" \
            or pcfg.is_encoder_decoder or pcfg.n_vision_tokens:
        pcfg = get_config("phi3-mini-3.8b").reduced()
        pparams = init_lm(jax.random.PRNGKey(0), pcfg)
        print(f"   ({args.arch} has non-attention mixers; paged act runs "
              f"on phi3-mini-3.8b instead)")
    # max_step_tokens arms the token-budget step scheduler: per step,
    # chunked-prefill dispatches + decode lanes stay under the cap, so
    # the burst of 10 admissions cannot stall lanes already decoding
    peng = Engine(pparams, pcfg, ServeConfig(
        max_len=96, decode_batch=4, max_new_tokens=8, kv_dtype="int8",
        prefill_len=16, paged=True, page_size=8, telemetry=True,
        max_step_tokens=16 + 4))
    system_prompt = rng.integers(0, pcfg.vocab, size=24).astype(np.int32)
    shared_reqs = [Request(
        uid=i, prompt=np.concatenate(
            [system_prompt,
             rng.integers(0, pcfg.vocab, size=6).astype(np.int32)]),
        max_new_tokens=8) for i in range(10)]
    sout = peng.generate(shared_reqs)
    pst = peng.stats()
    print(f"   {len(sout)} requests over a shared 24-token system prompt: "
          f"prefix hit rate {pst['prefix_hit_rate']:.2f}, "
          f"{pst['prefill_tokens_computed']}/{pst['prompt_tokens_total']} "
          f"prompt tokens computed, {pst['prefill_chunks']} chunks, "
          f"{pst['evictions']} evictions, "
          f"{pst['budget_deferred_admissions']:.0f} admissions deferred "
          f"by the step budget")
    phases = " ".join(
        f"{ph} {pst[f'step_{ph}_seconds']['p50'] * 1e3:.2f}ms"
        for ph in ("budget", "admission", "prefill", "decode", "transfer",
                   "emit"))
    print(f"   step-phase p50: {phases}  "
          f"(ttft p50 {pst['ttft_seconds']['p50'] * 1e3:.0f}ms, "
          f"{pst['compiled_shapes_decode']} decode shape(s) compiled)")

    print("[5/5] self-speculative decoding: Q-only draft, Q+LR verify …")
    spec_prompts = [rng.integers(0, pcfg.vocab, size=8 + i % 4)
                    .astype(np.int32) for i in range(4)]
    mk_reqs = lambda: [Request(uid=i, prompt=pr.copy(),   # noqa: E731
                               max_new_tokens=24)
                       for i, pr in enumerate(spec_prompts)]
    lanes = {}
    for label, spec in (("plain", False), ("speculative", True)):
        seng = Engine(pparams, pcfg, ServeConfig(
            max_len=96, decode_batch=1, max_new_tokens=24,
            kv_dtype="int8", prefill_len=16, paged=True, page_size=8,
            speculative=spec, spec_k=6))
        seng.warmup()
        t0 = time.perf_counter()
        sres = seng.generate(mk_reqs())
        wall = time.perf_counter() - t0
        lanes[label] = (sum(len(r.tokens) for r in sres) / wall,
                        seng.stats(), sres)
    tps_p, _, res_p = lanes["plain"]
    tps_s, sstat, res_s = lanes["speculative"]
    for a, b in zip(res_p, sorted(res_s, key=lambda r: r.uid)):
        assert np.array_equal(a.tokens, b.tokens), \
            "speculation must not change greedy output"
    print(f"   plain {tps_p:6.1f} tok/s | speculative {tps_s:6.1f} tok/s "
          f"({tps_s / tps_p:.2f}x) — {sstat['spec_rounds']} rounds, "
          f"acceptance rate {sstat['spec_acceptance_rate']:.3f} "
          f"({sstat['spec_accepted_tokens']}/{sstat['spec_draft_tokens']} "
          f"drafts), tokens identical")
    print("   (the SRR draft skips the LR correction, so acceptance — "
          "and the payoff — tracks how well Q alone matches Q+LR; "
          "benchmarks/serve_spec.py isolates the mechanism's ceiling)")


if __name__ == "__main__":
    main()
