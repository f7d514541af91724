"""What a traced run hands to the per-layer metric readers, and the
arithmetic they share.

A reader (``metrics/<name>.py``) gets a :class:`TracedRun` and returns a
number, or ``None`` when its trace holds nothing to read (then the metric
is left out of the result line).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from harness import trace as T
from harness.counts import StepWork, roofline_s

# A Pallas kernel shows in the trace as a custom call named after the
# jitted wrapper in kernels/ops.py that launches it. The Q+LR wrapper
# serves prefill and decode alike; its decode calls are the ones whose
# output has one row per decode lane (kernels/mxint_matmul.py
# _fused_kernel, chosen for <= 128 rows).
ATTN_KERNEL = r"^%_decode_attention_pallas_paged(\.\d+)? = .*custom-call"
DECODE_PROGRAM = r"^jit__decode\("    # the engine's decode step program


def qlr_decode_kernel(lanes: int) -> str:
    return (rf"^%mxint_lowrank_matmul(\.\d+)? = f32\[{lanes},\d+\]"
            r".*custom-call")


@dataclasses.dataclass
class DecodeCall:
    live: float        # lanes decoding
    ctx: float         # their live context summed (tokens each attends)


@dataclasses.dataclass
class TracedRun:
    trace: T.Trace
    window_s: float
    work: StepWork
    model: Dict
    lanes: int                       # compiled decode batch
    peaks: Dict
    # the engine's counters over the window; the decode step's work reads
    # decode_steps, decode_slot_steps (live lanes summed over the steps)
    # and decode_context_tokens (their context summed likewise)
    counters: Dict[str, float]
    gen_lags_s: List[float]          # open loop: sent minus due

    @property
    def ops(self) -> List[T.Ev]:
        return self.trace.ops.get(0, [])

    @property
    def modules(self) -> List[T.Ev]:
        return self.trace.modules.get(0, [])


def _mean_call(run: TracedRun) -> Optional[DecodeCall]:
    """The mean decode step of the window, from the engine's counters."""
    steps = run.counters.get("decode_steps", 0)
    if not steps:
        return None
    return DecodeCall(live=run.counters["decode_slot_steps"] / steps,
                      ctx=run.counters["decode_context_tokens"] / steps)


def kernel_share(run: TracedRun, pattern: str, flops_per_call: float,
                 bytes_per_call: float) -> Optional[float]:
    """Roofline share (%) of one kernel over all its traced calls."""
    ev = T.matching(run.ops, pattern)
    if not ev:
        return None
    t = T.total_s(ev)
    least, _ = roofline_s(len(ev) * flops_per_call, len(ev) * bytes_per_call,
                          run.peaks["bf16_flops"],
                          run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def qlr_roofline(run: TracedRun) -> Optional[float]:
    """The decode Q+LR kernel: one call per quantized matrix per layer
    per decode step, over the compiled decode batch (bound: memory)."""
    w = run.work
    per_step = w.qlr_calls_per_step
    flops = 2.0 * run.lanes * w.qlr_macs_per_row / per_step
    nbytes = (w.qlr_weight_bytes + run.lanes * w.qlr_io_per_row) / per_step
    return kernel_share(run, qlr_decode_kernel(run.lanes), flops, nbytes)


def attn_roofline(run: TracedRun) -> Optional[float]:
    """Paged flash-decode: one call per layer per decode step; its work
    is each lane's live context in the cache's storage type."""
    mc = _mean_call(run)
    if mc is None:
        return None
    L = run.model["n_layers"]
    flops = 2.0 * run.work.attn_macs_per_ctx * mc.ctx / L
    nbytes = run.work.kv_bytes_per_token * mc.ctx / L
    return kernel_share(run, ATTN_KERNEL, flops, nbytes)


def _decode_steps(run: TracedRun):
    mods = T.matching(run.modules, DECODE_PROGRAM)
    mc = _mean_call(run)
    if not mods or mc is None:
        return None
    return mods, mc


def step_mfu(run: TracedRun) -> Optional[float]:
    """Model FLOPs of the traced decode steps (live lanes' matmuls, LM
    head, attention over live context) over their device time x peak."""
    got = _decode_steps(run)
    if got is None:
        return None
    mods, mc = got
    w = run.work
    flops = 2.0 * (mc.live * (w.qlr_macs_per_row + w.head_macs_per_row)
                   + w.attn_macs_per_ctx * mc.ctx)
    return 100.0 * len(mods) * flops / (T.total_s(mods)
                                         * run.peaks["bf16_flops"])


def step_hbm(run: TracedRun) -> Optional[float]:
    """Weight and live-KV bytes of the traced decode steps over their
    device time x peak bandwidth."""
    got = _decode_steps(run)
    if got is None:
        return None
    mods, mc = got
    w = run.work
    nbytes = (w.qlr_weight_bytes + w.dense_weight_bytes
              + w.kv_bytes_per_token * mc.ctx)
    return 100.0 * len(mods) * nbytes / (T.total_s(mods)
                                          * run.peaks["hbm_bytes_per_s"])


def idle_share(run: TracedRun) -> Optional[float]:
    if not run.ops or run.window_s <= 0:
        return None
    return 100.0 * (1.0 - T.busy_s(run.ops) / run.window_s)
