"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Full paper pipeline: init → calibrate → SRR-quantize (W ≈ Q + LR) →
serve requests through the continuous-batching engine. The model is the
reduced smoke config unless ``--full-size`` asks for the published
widths; ``--layers N`` cuts depth either way.
``--method qer`` / ``--method w-only`` serve the baselines instead;
``--kv int8`` exercises the quantized KV cache (``--kv int4`` the
packed4 nibble cache — half the int8 HBM again); ``--scheduler
bucketed`` falls back to the prompt-length-bucketed baseline scheduler.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.api import PTQConfig
from repro.data import capture_calibration, data_config_for
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm, lm_loss
from repro.models.quantize import quantize_model_params
from repro.quant.base import QuantizerConfig
from repro.serve import Engine, Request, SamplingParams, ServeConfig, \
    percentile


def add_model_args(p: argparse.ArgumentParser) -> None:
    """Model/quantization flags shared by the batch driver here and the
    HTTP server (``repro.launch.server``)."""
    p.add_argument("--arch", default="phi3-mini-3.8b")
    p.add_argument("--full-size", action="store_true",
                   help="the config at its published widths (needs the "
                        "chip); default is the reduced smoke config")
    p.add_argument("--layers", type=int, default=None,
                   help="cut depth to N layers; widths stay as configured")
    p.add_argument("--method", default="srr",
                   choices=["srr", "qer", "w-only", "none"])
    p.add_argument("--rank", type=int, default=16)
    p.add_argument("--bits", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quant-report", metavar="PATH", default=None,
                   help="write the per-layer quantization-quality report "
                        "(singular-spectrum head, preserved/exposed "
                        "energy, residual norms, container bytes) as "
                        "JSON to PATH, plus a Chrome trace of the "
                        "quantizer passes to PATH with a .trace.json "
                        "extension; render with python -m "
                        "tools.quant_report PATH")


def build_quantized_model(args, tag: str = "serve", progress=None):
    """Init the model the shared flags name (published widths under
    ``--full-size``, else the reduced smoke config; ``--layers`` cuts
    depth) and run the paper pipeline (calibrate → quantize) on it;
    returns ``(params, cfg)``. ``progress``
    is called with each matrix's :class:`repro.core.api.LayerReport`.

    ``--quant-report PATH`` threads a :class:`repro.obs.QuantRecorder`
    through the pass and writes its schema-pinned JSON report (always —
    ``--method none`` yields an empty-layer report, so CI artifact steps
    never conditionally skip)."""
    cfg = get_config(args.arch)
    if not getattr(args, "full_size", False):
        cfg = cfg.reduced()
    if getattr(args, "layers", None):
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    recorder = None
    report_path = getattr(args, "quant_report", None)
    if report_path:
        from repro.obs import QuantRecorder
        recorder = QuantRecorder()
    if args.method != "none":
        dcfg = data_config_for(cfg, seq_len=32, global_batch=4,
                               seed=args.seed)
        stats = capture_calibration(
            params, cfg, dcfg, lambda c, pp, b, cc: lm_loss(c, pp, b, cc),
            n_batches=2)
        ptq = PTQConfig(method=args.method, scaling="qera-exact",
                        rank=args.rank,
                        quantizer=QuantizerConfig(kind="mxint",
                                                  bits=args.bits,
                                                  block_size=32),
                        seed=args.seed)
        t0 = time.perf_counter()
        params, reports = quantize_model_params(params, stats, ptq,
                                                progress=progress,
                                                recorder=recorder)
        print(f"[{tag}] {args.method} quantized {len(reports)} matrices "
              f"in {time.perf_counter() - t0:.1f}s")
    if recorder is not None:
        recorder.write(report_path)
        print(f"[{tag}] quant report -> {report_path}")
    return params, cfg


def main(argv=None):
    p = argparse.ArgumentParser()
    add_model_args(p)
    p.add_argument("--kv", default="f32",
                   choices=["f32", "bf16", "int8", "int4"])
    p.add_argument("--max-len", type=int, default=128,
                   help="cache slots per lane (prompt + generation)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--scheduler", default="continuous",
                   choices=["continuous", "bucketed"])
    p.add_argument("--prefill-len", type=int, default=32,
                   help="compiled prompt pad length (continuous)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="per-request sampling temperature (0 = greedy); "
                        "applied through SamplingParams on every request")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 = off)")
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k logit filter (0 = off)")
    p.add_argument("--spec-k", type=int, default=0,
                   help="self-speculative decoding: draft up to K tokens "
                        "per round through the Q-only base (the low-rank "
                        "sliver skipped), verify them in one chunked Q+LR "
                        "dispatch, rewind any rejected tail (0 = off; "
                        "continuous scheduler, greedy lanes only — "
                        "sampled lanes fall back to per-token decode)")
    p.add_argument("--max-step-tokens", type=int, default=None,
                   help="token-budget step scheduler: per-step cap on "
                        "prefill dispatch width + decode lanes "
                        "(continuous scheduler only; bounds p95 ITL "
                        "under long-prompt bursts)")
    p.add_argument("--fused", default="auto", choices=["auto", "on", "off"],
                   help="fused serving path — Q+LR matmuls AND decode "
                        "attention over the slot cache: auto (Pallas "
                        "kernels on TPU, fused-XLA elsewhere), on (force "
                        "kernels; interpret off-TPU), off (dequant-then-"
                        "matmul / dequantize-the-cache baselines). With "
                        "--kv int8/int4 the flash-decode path reads the "
                        "codes directly (int4: packed two-per-byte, "
                        "unpacked in VMEM); the dense f32 cache never "
                        "materializes")
    p.add_argument("--paged", action="store_true",
                   help="paged KV cache: block-granular page pool + "
                        "per-slot block tables, radix-tree prefix reuse "
                        "(identical prompt prefixes map cached pages in "
                        "and skip their prefill), and chunked prefill "
                        "(prompts longer than --prefill-len stream in "
                        "prefill_len-sized chunks interleaved with decode)")
    p.add_argument("--page-size", type=int, default=64,
                   help="logical KV slots per page (even; = flash-decode "
                        "kernel block in the paged path)")
    p.add_argument("--n-pages", type=int, default=None,
                   help="physical page-pool size (paged only; default "
                        "sized so every slot can hold a full row plus "
                        "prefix-cache headroom)")
    p.add_argument("--compute-dtype", default="f32",
                   choices=["f32", "bf16"],
                   help="activation dtype for prefill/decode matmuls")
    p.add_argument("--sanitize", action="store_true",
                   help="audit serve-state invariants after every engine "
                        "step (page refcount conservation, block-table "
                        "validity, pos monotonicity, int4 nibble "
                        "alignment); token-identical but host-syncing — "
                        "a CI/debug mode, not a production default")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable radix-tree prefix reuse (paged only)")
    p.add_argument("--drift-monitor", action="store_true",
                   help="sampled shadow comparison of the serving logits "
                        "against a reference lowering of the same "
                        "quantized params (KL / top-1 agreement / "
                        "max-|Δlogit| histograms + NaN/inf guard "
                        "counters in the metrics snapshot); "
                        "token-identical, costs one extra decode "
                        "dispatch per sampled step")
    p.add_argument("--drift-sample-rate", type=float, default=0.05,
                   help="fraction of decode steps the drift monitor "
                        "shadow-compares (deterministic in the step "
                        "counter; 1.0 = every step)")
    p.add_argument("--drift-ref-fused", default="off",
                   choices=["auto", "on", "off"],
                   help="fused mode of the drift monitor's reference "
                        "lowering; the default 'off' is the dequant-"
                        "then-matmul ground-truth path")
    p.add_argument("--telemetry", action="store_true",
                   help="enable serve telemetry: request-lifecycle + "
                        "step-phase tracing, latency histograms, compile "
                        "tracking (implied by --trace/--profile-dir)")
    p.add_argument("--metrics-json", metavar="PATH", default=None,
                   help="write the final metrics snapshot as JSON to "
                        "PATH, plus the Prometheus text exposition to "
                        "PATH with a .prom extension")
    p.add_argument("--tokens-json", metavar="PATH", default=None,
                   help="write {uid: generated tokens} as JSON to PATH "
                        "(CI token-parity assertions, e.g. --sanitize "
                        "on/off must generate identical streams)")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="write the Chrome trace-event JSON (Perfetto-"
                        "loadable) to PATH, plus the JSONL event stream "
                        "to PATH with a .jsonl extension")
    p.add_argument("--trace-sync", action="store_true",
                   help="fence device dispatches (block_until_ready) so "
                        "traced phase timings show device time where it "
                        "was launched, not in the next host transfer")
    p.add_argument("--profile-dir", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of the first "
                        "--profile-steps engine steps into DIR (view in "
                        "TensorBoard/Perfetto; works on CPU and TPU)")
    p.add_argument("--profile-steps", type=int, default=20,
                   help="engine steps to capture under --profile-dir")
    args = p.parse_args(argv)
    enable_compile_cache()

    params, cfg = build_quantized_model(args)

    telemetry = bool(args.telemetry or args.trace or args.profile_dir)
    eng = Engine(params, cfg, ServeConfig(
        max_len=args.max_len, decode_batch=args.batch,
        max_new_tokens=args.new_tokens, kv_dtype=args.kv,
        scheduler=args.scheduler, prefill_len=args.prefill_len,
        temperature=args.temperature, seed=args.seed,
        max_step_tokens=args.max_step_tokens,
        speculative=args.spec_k > 0,
        spec_k=args.spec_k if args.spec_k > 0 else 4,
        fused=args.fused, paged=args.paged, page_size=args.page_size,
        n_pages=args.n_pages, compute_dtype=args.compute_dtype,
        sanitize=args.sanitize,
        drift_monitor=args.drift_monitor,
        drift_sample_rate=args.drift_sample_rate,
        drift_ref_fused=args.drift_ref_fused,
        prefix_cache=not args.no_prefix_cache,
        telemetry=telemetry, trace_sync=args.trace_sync,
        profile_dir=args.profile_dir, profile_steps=args.profile_steps))
    rng = np.random.default_rng(args.seed)
    sp = SamplingParams(temperature=args.temperature, top_p=args.top_p,
                        top_k=args.top_k)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=8 + 4 * (i % 3))
                    .astype(np.int32), params=sp)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    results = eng.generate(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    print(f"[serve] {len(results)} requests, {toks} tokens "
          f"in {dt:.1f}s ({toks / dt:.1f} tok/s incl. compile, "
          f"scheduler={args.scheduler})")
    # latency_s is None-able (a max_new_tokens=0 request has no decode
    # span); the shared interpolating percentile replaces the old index
    # shortcut, which overshot p95 for small n and mis-picked even-n
    # medians
    lats = [r.latency_s for r in results if r.latency_s is not None]
    if args.scheduler == "continuous" and lats:
        p50 = percentile(lats, 0.50)
        p95 = percentile(lats, 0.95)
        st = eng.stats()
        print(f"[serve] latency p50 {p50 * 1e3:.0f}ms p95 {p95 * 1e3:.0f}ms "
              f"occupancy {st['occupancy']:.2f} "
              f"eos_retired {st['eos_retired']}")
        if args.spec_k > 0:
            print(f"[serve] speculative: {st['spec_rounds']} rounds, "
                  f"{st['spec_accepted_tokens']}/{st['spec_draft_tokens']} "
                  f"drafts accepted "
                  f"(rate {st['spec_acceptance_rate']:.3f})")
        if args.drift_monitor:
            print(f"[serve] drift: {st['drift_checks']} checks, "
                  f"top-1 agreement {st['drift_top1_agreement_rate']:.3f}, "
                  f"{st['drift_nonfinite']} non-finite, "
                  f"{st['guard_token_oob']} OOB tokens")
        if args.paged:
            print(f"[serve] paged: {st['prefill_chunks']} prefill chunks, "
                  f"{st['prefill_tokens_computed']}/"
                  f"{st['prompt_tokens_total']} prompt tokens computed "
                  f"(prefix hit rate {st['prefix_hit_rate']:.2f}), "
                  f"{st['evictions']} evictions, "
                  f"{st['pages_hot']}/{st['pages_total']} pages hot")
    for r in results[:3]:
        print(f"  req {r.uid} [{r.finish_reason}]: "
              f"{r.tokens[:10].tolist()}")
    if args.tokens_json:
        with open(args.tokens_json, "w") as f:
            json.dump({int(r.uid): [int(t) for t in r.tokens]
                       for r in results}, f, sort_keys=True)
            f.write("\n")
        print(f"[serve] tokens -> {args.tokens_json}")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(eng.stats(), f, indent=2, sort_keys=True)
            f.write("\n")
        prom = os.path.splitext(args.metrics_json)[0] + ".prom"
        with open(prom, "w") as f:
            f.write(eng.prometheus())
        print(f"[serve] metrics -> {args.metrics_json} (+ {prom})")
    if args.trace:
        jsonl = os.path.splitext(args.trace)[0] + ".jsonl"
        eng.write_trace(args.trace, jsonl_path=jsonl)
        print(f"[serve] trace -> {args.trace} (+ {jsonl})")
    if args.profile_dir:
        eng.tel.stop_profiler()
        print(f"[serve] jax.profiler trace -> {args.profile_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
