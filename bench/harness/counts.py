"""The operations and bytes a decode step needs, from the served shapes.

Everything is counted from the parameter tree the engine serves (its
leaves' shapes and dtypes, read at set-up), from the configuration's
architecture module (the KV cache's bytes and the attention's
multiply-adds per token of context) and from each step's live context,
never from constants. These are the work of the algorithm, not of a
particular kernel: a kernel that reads more than it needs shows as a
lower roofline share.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import numpy as np

from harness.weights import path_str

QLR_READ = ("codes", "packed", "scale", "l", "r")


def nbytes(x) -> int:
    """Bytes of an array or of a ShapeDtypeStruct."""
    return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class StepWork:
    """Per-decode-step constants of one served model."""
    qlr_weight_bytes: int     # Q codes + block scales + L + R, every layer
    qlr_macs_per_row: int     # multiply-adds of the Q+LR matmuls per token
    qlr_io_per_row: int       # activation bytes in + out per token (f32)
    qlr_calls_per_step: int   # Q+LR matmuls per step: containers x layers
    dense_weight_bytes: int   # LM head, norms, biases (read every step)
    head_macs_per_row: int
    kv_bytes_per_token: int   # one token's cached state over all layers
    attn_macs_per_ctx: int    # multiply-adds per (query, key) over layers


def cache_bytes(values: int, scales: int, kv_dtype: str) -> int:
    """Bytes of ``values`` cached numbers in the cache's storage type,
    with ``scales`` f32 scales where the type is quantized (int4: half a
    byte per value)."""
    if kv_dtype == "int4":
        return values // 2 + 4 * scales
    if kv_dtype == "int8":
        return values + 4 * scales
    return values * {"bf16": 2, "f32": 4}[kv_dtype]


def leaf_work(params) -> Dict[str, int]:
    """The weights' part of :class:`StepWork`, from a walk over the
    served tree: every ``Q + LR`` container (a parent holding ``codes`` or
    ``packed``) is a matmul per layer of its stack; every other leaf but
    the embedding is read whole each step."""
    qlr_bytes = macs = io = calls = dense = 0
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    by_parent: Dict[str, Dict[str, object]] = {}
    for p, x in flat:
        path = path_str(p)
        parent, leaf = path.rsplit(".", 1)
        by_parent.setdefault(parent, {})[leaf] = x
    for parent, leaves in by_parent.items():
        if "codes" in leaves or "packed" in leaves:
            qlr_bytes += sum(nbytes(leaves[k]) for k in QLR_READ
                             if k in leaves)
            l, r = leaves["l"], leaves["r"]
            layers = int(np.prod(l.shape[:-2]))
            m, rank, n = l.shape[-2], l.shape[-1], r.shape[-1]
            macs += layers * (m * n + rank * (m + n))
            io += layers * 4 * (m + n)
            calls += layers
            if "b" in leaves:
                dense += nbytes(leaves["b"])
        elif parent != "embed":
            dense += sum(nbytes(v) for v in leaves.values())
    return dict(qlr_weight_bytes=qlr_bytes, qlr_macs_per_row=macs,
                qlr_io_per_row=io, qlr_calls_per_step=calls,
                dense_weight_bytes=dense,
                head_macs_per_row=int(np.prod(by_parent["lm_head"]["w"]
                                              .shape)))


def step_work(params, model: Dict, kv_dtype: str, arch) -> StepWork:
    """The work of one decode step of ``params``; ``arch`` is the
    configuration's architecture module, whose own ``step_work`` is used
    where it has one."""
    own = getattr(arch, "step_work", None)
    if own is not None:
        return own(params, model, kv_dtype)
    return StepWork(**leaf_work(params),
                    kv_bytes_per_token=arch.kv_bytes_per_token(model,
                                                               kv_dtype),
                    attn_macs_per_ctx=arch.attn_macs_per_ctx(model))


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple:
    """(least time the chip could take, which bound sets it)."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
