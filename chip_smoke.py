"""Chip smoke: the SRR serving path on one TPU chip at phi3-mini's widths.

A smoke, not a benchmark. It proves that the main path starts and serves
correctly on the chip; the seconds it prints are set-up costs, not speed.

  1. Refuse unless JAX's first device is a TPU.
  2. SRR PTQ through ``build_quantized_model``: calibrate, SRR-quantize
     (qera-exact scaling, 3-bit MXINT, rank 16, seed 0), ``Q + LR``
     containers. phi3-mini at its published widths, depth cut to 8
     layers (the PTQ pass holds f32 weights and every layer's Σxxᵀ on the
     device, so full depth does not fit one chip's 16 GB).
  3. Serve 8 greedy requests through ``EngineServer``: paged int4 KV,
     prefix reuse, chunked prefill, fused kernels. One request streams
     over a real socket; four share a 256-token prefix, the last of them
     submitted after the first has finished.
  4. Check that the Pallas kernels ran, that the served logits agree with
     the dequant-then-matmul reference (the engine's drift monitor on
     every decode step), and that the prefix cache hit.

The last line of standard output is one JSON object naming the device.

    python chip_smoke.py
"""
from __future__ import annotations

import argparse
import http.client
import json
import queue
import resource
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = dict(arch="phi3-mini-3.8b", full_size=True, layers=8,
             method="srr", rank=16, bits=3, seed=0, quant_report=None)
N_REQUESTS, NEW_TOKENS = 8, 32
PROMPT_MIN, PROMPT_MAX, PREFIX, N_SHARED = 128, 1024, 256, 4
ENGINE = dict(paged=True, kv_dtype="int4", decode_batch=8, max_len=2048,
              prefill_len=256, fused="auto", max_new_tokens=NEW_TOKENS,
              drift_monitor=True, drift_sample_rate=1.0,
              drift_ref_fused="off")
# Served vs reference logits, per lane at every decode step. Both
# lowerings read the same quantized weights and the same int4 KV cache,
# so they differ only by rounding: f32 matmuls as bf16 passes on the MXU
# and a different accumulation order. The logits of this randomly
# initialised model have unit scale (final RMSNorm, head std 1/√d), so
# rounding moves them by hundredths (KL ~1e-4). A wrong block, page,
# nibble order or scale moves them by order one (KL >= 0.1). The bounds
# sit between the two.
MAX_KL = 1e-2
MAX_LOGIT_DELTA = 0.25
RESULT_TIMEOUT_S = 600


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def host_max_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def fail(msg: str) -> None:
    raise SystemExit(f"[smoke] FAIL: {msg}")


def make_prompts(vocab: int, seed: int) -> list[np.ndarray]:
    """Seeded prompts of PROMPT_MIN..PROMPT_MAX tokens; the first
    N_SHARED open with one PREFIX-token system prompt."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, PREFIX)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    lens[:N_SHARED] = np.maximum(lens[:N_SHARED], PREFIX + PROMPT_MIN)
    prompts = []
    for i, n in enumerate(lens):
        p = rng.integers(0, vocab, n)
        if i < N_SHARED:
            p[:PREFIX] = prefix
        prompts.append(p.astype(np.int32))
    return prompts


def stream_completion(host: str, port: int, prompt: np.ndarray) -> list:
    """One streamed /v1/completions request; returns its token ids."""
    conn = http.client.HTTPConnection(host, port, timeout=RESULT_TIMEOUT_S)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": prompt.tolist(), "stream": True,
        "max_tokens": NEW_TOKENS, "temperature": 0.0}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        fail(f"stream: HTTP {resp.status} {resp.read()!r}")
    frames = [f.strip()[len("data: "):]
              for f in resp.read().decode().split("\n\n")
              if f.strip().startswith("data: ")]
    conn.close()
    if not frames or frames[-1] != "[DONE]":
        fail(f"stream must end with [DONE], got {frames[-2:]}")
    tokens = []
    for f in frames[:-1]:
        choice = json.loads(f)["choices"][0]
        tokens += choice.get("token_ids", [])
    return tokens


def collect(q: "queue.Queue") -> list:
    tokens = []
    while True:
        kind, val = q.get(timeout=RESULT_TIMEOUT_S)
        if kind == "token":
            tokens.append(int(val[0]))
        elif kind == "done":
            return tokens
        else:
            fail(f"engine error: {val}")


def serve(eng, prompts) -> tuple[dict, dict]:
    """Requests through the HTTP server's EngineServer; request 0 streams
    over the socket, the last shared-prefix request waits for it."""
    from repro.serve import SamplingParams, serve_http
    httpd, srv = serve_http(eng, host="127.0.0.1", port=0)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    host, port = httpd.server_address[:2]
    sp = SamplingParams(temperature=0.0, max_new_tokens=NEW_TOKENS)
    out: dict = {}
    try:
        first = threading.Thread(target=lambda: out.__setitem__(
            0, stream_completion(host, port, prompts[0])))
        first.start()
        later = N_SHARED - 1
        queues = {i: srv.submit(prompts[i], sp)[1]
                  for i in range(1, len(prompts)) if i != later}
        first.join(RESULT_TIMEOUT_S)
        if 0 not in out:
            fail("the streamed request did not finish")
        queues[later] = srv.submit(prompts[later], sp)[1]
        for i, q in queues.items():
            out[i] = collect(q)
        stats = srv.stats()
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.close()
        server.join(5)
    return out, stats


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.splitlines()[0]).parse_args(
        argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] needs a TPU; JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_quantized_model
    from repro.models.linear import fused_mode
    from repro.serve import Engine, ServeConfig
    enable_compile_cache()
    log("chip smoke, not a benchmark: seconds below are set-up costs")

    t0 = time.perf_counter()
    params, cfg = build_quantized_model(
        argparse.Namespace(**MODEL), tag="smoke",
        progress=lambda r: log(f"  {r.name} {r.shape} k*={r.k_star} "
                               f"{r.seconds:.3f}s"))
    jax.block_until_ready(params)
    ptq_s = time.perf_counter() - t0
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.head_dim_} (kv {cfg.n_kv_heads}), "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    log(f"ptq_s {ptq_s:.3f} (calibrate + SRR quantize, eager, incl. "
        f"compiles); host max RSS {host_max_rss_gb():.3f} GB")

    eng = Engine(params, cfg, ServeConfig(**ENGINE))
    mode = fused_mode(eng.ctx)
    log(f"fused_mode {mode}")
    if mode != "kernel":
        fail(f"fused_mode resolved to {mode!r}, not 'kernel'")
    t0 = time.perf_counter()
    eng.warmup()
    log(f"compile_s {time.perf_counter() - t0:.3f} (warmup: prefill chunk "
        f"+ decode + drift probe)")
    t0 = time.perf_counter()
    hlo = eng._decode.lower(eng.params, eng._tok, eng.slots.cache,
                            eng._decode_lanes(), False).compile().as_text()
    n_custom = hlo.count('custom_call_target="tpu_custom_call"')
    log(f"decode step: {n_custom} tpu_custom_call ops "
        f"({time.perf_counter() - t0:.3f}s to lower + compile again)")
    if n_custom == 0:
        fail("the compiled decode step holds no Pallas kernel")

    prompts = make_prompts(cfg.vocab, MODEL["seed"])
    log(f"prompt lengths {[len(p) for p in prompts]}; requests 0-"
        f"{N_SHARED - 1} share a {PREFIX}-token prefix")
    t0 = time.perf_counter()
    out, st = serve(eng, prompts)
    log(f"served {len(out)} requests x {NEW_TOKENS} tokens in "
        f"{time.perf_counter() - t0:.3f}s")
    for i in range(len(prompts)):
        toks = np.asarray(out.get(i, []))
        if len(toks) != NEW_TOKENS or np.any((toks < 0) | (toks >= cfg.vocab)):
            fail(f"request {i}: {len(toks)} tokens, want {NEW_TOKENS} "
                 f"in [0, {cfg.vocab})")

    peak = dev.memory_stats().get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak}; host max RSS {host_max_rss_gb():.3f} GB")
    log(f"prefix_hit_rate {st['prefix_hit_rate']} "
        f"({st['prefix_hit_tokens']}/{st['prompt_tokens_total']} prompt "
        f"tokens), {st['prefill_chunks']} prefill chunks")
    kl, delta = st["drift_kl"], st["drift_logit_delta"]
    log(f"drift: {st['drift_checks']} checks, top-1 agreement "
        f"{st['drift_top1_agreement_rate']}, KL max {kl['max']} p50 "
        f"{kl['p50']}, max|dlogit| max {delta['max']} p50 {delta['p50']}, "
        f"drift_nonfinite {st['drift_nonfinite']}, guard_token_oob "
        f"{st['guard_token_oob']}")
    if st["prefix_hit_rate"] <= 0:
        fail("no prefix-cache hit")
    if st["drift_checks"] == 0:
        fail("the drift monitor compared nothing")
    if st["drift_nonfinite"] or st["guard_token_oob"]:
        fail("non-finite logits or out-of-range tokens")
    if kl["count"] != st["drift_checks"] or kl["max"] > MAX_KL:
        fail(f"KL {kl['max']} over {MAX_KL} (or a non-finite lane)")
    if delta["max"] > MAX_LOGIT_DELTA:
        fail(f"max|dlogit| {delta['max']} over {MAX_LOGIT_DELTA}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
