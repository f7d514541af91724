"""One run of one cell: set-up, the measured window, the check, the line."""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from harness import layers, registry
from harness import trace as T
from harness.counts import step_work
from harness.traffic import Traffic
from harness.weights import leaf_specs, make_params
from harness.window import GRACE_S, Window, percentile, token_gaps, tokens_in

# engine counters read around the window
COUNTERS = ("prefix_hit_tokens", "prompt_tokens_total", "prefill_chunks",
            "decode_steps", "decode_slot_steps", "decode_context_tokens")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Cell:
    def __init__(self, cell: Dict, conf: Dict, mix: Dict, seed: int,
                 seconds: float, traced: bool, dev, n_dev: int,
                 t_start: float, engine_overrides: Dict = None):
        self.cell, self.conf, self.mix = cell, conf, mix
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.dev, self.n_dev = dev, n_dev
        self.t_start = t_start
        self.overrides = engine_overrides or {}
        self.parts: Dict[str, float] = {}
        self._t = t_start

    def part(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = now - self._t
        log(f"setup {name}: {self.parts[name]:.3f} s")
        self._t = now

    # ------------------------------------------------------------ set-up
    def build(self):
        # the configuration's architecture, before anything is built
        self.arch = arch = registry.reference(self.conf)
        import jax
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import init_lm
        from repro.models.quantize import quantized_abstract
        from repro.serve import Engine, ServeConfig

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.part("process_start_and_imports")

        model, wconf = self.conf["model"], self.conf["weights"]
        cfg = get_config(self.conf["arch"])
        for k, attr in arch.MODEL_KEYS.items():
            if getattr(cfg, attr) != model[k]:
                raise ValueError(f"{self.conf['arch']}: program has {attr}="
                                 f"{getattr(cfg, attr)}, configuration file "
                                 f"says {k}={model[k]}")
        abstract = quantized_abstract(
            jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
            rank=wconf["rank"], block_size=wconf["block"],
            container=wconf["container"])
        got = {p: (s, d) for p, (s, d) in leaf_specs(abstract).items()}
        want = arch.leaf_specs(model, wconf)
        if got != want:
            diff = sorted(set(got.items()) ^ set(want.items()))[:6]
            raise ValueError(f"served tree differs from the reference's "
                             f"layout: {diff}")
        params = make_params(self.seed, abstract, arch.BRANCH_OUT)
        jax.block_until_ready(params)
        self.part("weights_on_device")

        self.work = step_work(params, model, self.conf["serve"]["kv_dtype"],
                              arch)
        sc = ServeConfig(**dict(self.conf["serve"], paged=True,
                                **self.overrides))
        eng = Engine(params, cfg, sc)
        eng.warmup()
        self.part("engine_and_warmup")
        self.sc, self.cfg, self.model = sc, cfg, model
        self.chunk = eng._chunk_len
        return eng

    def prefill_prefixes(self, eng, traffic: Traffic) -> None:
        """Prefill each shared prefix once, so the window finds it in the
        radix cache as a long-running server would."""
        from repro.serve import Request, SamplingParams
        for i, p in enumerate(traffic.prefixes):
            eng.submit(Request(uid=-1 - i, prompt=p, params=SamplingParams(
                temperature=0.0, max_new_tokens=1)))
        eng.drain()
        self.part("prefix_cache_fill")

    # ------------------------------------------------------------ the run
    def run(self) -> Dict:
        from jax import monitoring
        from repro.serve import SamplingParams

        eng = self.build()
        traffic = Traffic(self.mix, self.seed, self.model["vocab"])
        if traffic.max_total_tokens >= self.sc.max_len:
            raise ValueError("the mix's longest request does not fit max_len")
        if traffic.prefill_prefixes:
            self.prefill_prefixes(eng, traffic)
        win = Window(eng, traffic, lambda n: SamplingParams(
            temperature=0.0, max_new_tokens=n))
        if traffic.closed:
            win.start_closed()
            self.part("closed_loop_fill")
        tracer = Tracer(eng) if self.traced else None
        compiles = []
        monitoring.register_event_duration_secs_listener(
            lambda ev, d, **kw: compiles.append(ev)
            if "backend_compile" in ev else None)
        if tracer:
            tracer.start()
        n_compiles0 = len(compiles)
        stats0 = eng.stats()
        if traffic.closed:
            setup_s = time.perf_counter() - self.t_start
            t0, t1 = win.run_closed(self.seconds)
        else:
            pre = self.mix["preroll_s"]
            setup_s = time.perf_counter() - self.t_start + pre
            t0, t1 = win.run_open(self.seconds, pre)
        stats1 = eng.stats()
        n_compiles = len(compiles) - n_compiles0
        if tracer:
            tracer.stop()
        due = win.in_window(t0, t1)
        # a request due (or, closed loop, sent) in the window is waited
        # for and timed to its first token, however long after the close
        # that comes: late is not failed
        win.first_tokens(due, GRACE_S)
        flights = list(win.flights.values())
        attempted = win.active_in(t0, t1)
        failed = [f for f in due if not f.tokens]
        n_attempted = len(attempted)
        log(f"window {t1 - t0:.3f} s: {len(attempted)} requests worked on, "
            f"{len(due)} sent or due in it, {len(failed)} failed, "
            f"{n_compiles} compiles inside the window (want 0); "
            f"setup_s {setup_s:.3f}")
        device = self.device_stats()
        counters = {k: stats1.get(k, 0) - stats0.get(k, 0)
                    for k in COUNTERS}
        log(f"engine counters over the window: {counters}")
        extra = {}
        if self.traced:
            metrics, extra, dev_t = self.layer_metrics(
                tracer, counters, due, traffic.closed)
            device.update(dev_t)
        else:
            metrics = self.end_to_end(flights, due, t0, t1, setup_s)
        sample = self.sample(flights)
        bad = sum(1 for f in flights for t in f.tokens
                  if not 0 <= t < self.model["vocab"])
        del win, eng, tracer, flights, due, attempted
        gc.collect()
        checks = self.check(sample, bad)
        correct = all(v["value"] <= v["limit"] for v in checks.values())
        out = {"correct": bool(correct),
               "attempted": n_attempted,
               "failed": len(failed), "metrics": metrics,
               "device": dict(platform=self.dev.platform,
                              kind=self.dev.device_kind, count=self.n_dev,
                              **device)}
        out.update(extra)
        out["checks"] = checks
        return out

    def device_stats(self) -> Dict:
        ms = self.dev.memory_stats() or {}
        return {"memory_peak_bytes": int(ms.get("peak_bytes_in_use", 0))}

    # ----------------------------------------------------------- metrics
    def end_to_end(self, flights, due, t0, t1, setup_s) -> Dict:
        gaps = token_gaps(flights, t0, t1)
        vals = {"setup_s": setup_s,
                "tok_s": tokens_in(flights, t0, t1) / (t1 - t0)}
        if gaps:
            vals["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
            log(f"inter-token gaps: {len(gaps)}, p50 "
                f"{1e3 * percentile(gaps, 50):.3f} ms")
        ttft = [(f.times[0] - f.due) if f.times else float("inf")
                for f in due]
        if ttft:
            vals["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
            lags = [f.sent - f.due for f in due]
            log(f"time to first token over {len(ttft)} requests: p50 "
                f"{1e3 * percentile(ttft, 50):.3f} ms; generator lateness "
                f"p95 {1e3 * percentile(lags, 95):.3f} ms")
        log("end to end: " + ", ".join(f"{k} {v:.6g}"
                                       for k, v in vals.items()))
        out = {}
        for m in self.cell["end_to_end"]:
            if m["name"] not in vals:
                raise RuntimeError(f"{m['name']}: nothing measured")
            out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        return out

    def layer_metrics(self, tracer, counters, due, closed):
        run = layers.TracedRun(
            trace=tracer.reduce(), window_s=tracer.t_off - tracer.t_on,
            work=self.work, model=self.model,
            lanes=self.sc.decode_batch,
            peaks=registry.peaks(self.dev.device_kind), counters=counters,
            gen_lags_s=[] if closed else [f.sent - f.due for f in due])
        out = {}
        for m in self.cell["per_layer"]:
            v = registry.metric_reader(m["name"])(run)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        ops = run.ops
        idle = T.gaps(ops)
        dev = {"busy_s": T.busy_s(ops), "window_s": run.window_s}
        log(f"trace: {len(ops)} device ops, {len(run.modules)} programs, "
            f"{len(run.trace.host)} host spans, {counters['decode_steps']} "
            f"decode steps; busy {dev['busy_s']:.6f} of "
            f"{dev['window_s']:.6f} s")
        extra = {"breakdown": {"device_ops": T.top_ops(ops),
                               "idle_gaps": T.idle_by_host(idle,
                                                           run.trace.host)}}
        return out, extra, dev

    # ------------------------------------------------------- correctness
    def sample(self, flights) -> List[Dict]:
        """The requests the reference recomputes, drawn from the seed: the
        one with the most served tokens, then others at random. A request
        still answering at the close is checked on the tokens it served."""
        have = [f for f in flights if f.tokens and f.matched is not None]
        if not have:
            return []
        rng = np.random.default_rng([self.seed, 0x5EED])
        longest = max(have, key=lambda f: (len(f.tokens), f.uid))
        rest = [f for f in have if f is not longest]
        k = min(self.mix["check_requests"] - 1, len(rest))
        pick = [longest] + [rest[i] for i in
                            sorted(rng.choice(len(rest), k, replace=False))]
        return [{"prompt": f.req.prompt, "served": np.asarray(f.tokens),
                 "matched": f.matched, "chunk": self.chunk} for f in pick]

    def check(self, sample, bad_tokens: int) -> Dict:
        """Recompute the sample with the reference. The number compared is
        the mean gap by which a served token's logit lies below the
        reference's best at its position, over every sampled token: the
        widest gap alone does not separate the float32 program from its
        bfloat16 control on the chip (PERF.md, section 4)."""
        from reference.common import served_gaps
        limits = registry.checks(self.cell["name"])
        t = time.perf_counter()
        mean_gap = widest = float("inf")
        if sample:
            ref = self.arch.Reference(self.model, self.conf["weights"])
            per = served_gaps(ref, self.seed, sample,
                              batch=self.mix["check_requests"],
                              groups=self.mix["prefixes"]["count"])
            every = np.concatenate(per)
            mean_gap, widest = float(np.mean(every)), float(np.max(every))
            for s, g in zip(sample, per):
                worst = np.argsort(g)[::-1][:3]
                log(f"check request: prompt {len(s['prompt'])} tokens "
                    f"(prefix hit {s['matched']}), {len(g)} served, "
                    f"{int(np.sum(g > 0))} below the reference's best; "
                    f"widest gaps at served index " + ", ".join(
                        f"{i}: {g[i]:.6g}" for i in worst))
        log(f"reference over {len(sample)} requests "
            f"({sum(len(s['served']) for s in sample)} served tokens): "
            f"widest gap {widest:.6g}; {time.perf_counter() - t:.3f} s")
        return {"mean_gap": {"value": mean_gap,
                             "limit": limits["mean_gap"]},
                "bad_tokens": {"value": bad_tokens, "limit": 0}}


class Tracer:
    """Profile the window; annotate the engine's steps and dispatches from
    here."""

    def __init__(self, eng):
        import jax
        ann = jax.profiler.TraceAnnotation
        step, decode, chunk = eng.step, eng._decode, eng._chunk

        def traced_step():
            with ann("engine_step"):
                return step()

        def traced_decode(*a):
            with ann("decode_dispatch"):
                return decode(*a)

        def traced_chunk(*a):
            with ann("prefill_dispatch"):
                return chunk(*a)

        eng.step, eng._decode, eng._chunk = traced_step, traced_decode, \
            traced_chunk

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.t_on = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t_off = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> T.Trace:
        try:
            return T.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
