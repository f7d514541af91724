"""The operations and bytes a decode step needs, from the served shapes.

Everything is counted from the parameter tree the engine serves (its
leaves' shapes and dtypes, read at set-up) and from each step's live
context, never from constants. These are the work of the algorithm, not
of a particular kernel: a kernel that reads more than it needs shows as a
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
    dense_weight_bytes: int   # LM head, norms, biases (read every step)
    head_macs_per_row: int
    kv_bytes_per_token: int   # one token's K and V over all layers
    attn_macs_per_ctx: int    # multiply-adds per (query, key) over layers


def kv_bytes_per_token(model: Dict, kv_dtype: str) -> int:
    """K and V of one token, all layers, in the cache's storage type
    (int4: half a byte per value plus an f32 scale per head)."""
    vals = 2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"]
    scales = 2 * model["n_layers"] * model["n_kv_heads"]
    if kv_dtype == "int4":
        return vals // 2 + 4 * scales
    if kv_dtype == "int8":
        return vals + 4 * scales
    return vals * {"bf16": 2, "f32": 4}[kv_dtype]


def step_work(params, model: Dict, kv_dtype: str) -> StepWork:
    qlr_bytes = macs = io = dense = 0
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
            if "b" in leaves:
                dense += nbytes(leaves["b"])
        elif parent != "embed":
            dense += sum(nbytes(v) for v in leaves.values())
    d, v = model["d_model"], model["vocab"]
    return StepWork(
        qlr_weight_bytes=qlr_bytes, qlr_macs_per_row=macs,
        qlr_io_per_row=io, dense_weight_bytes=dense,
        head_macs_per_row=d * v,
        kv_bytes_per_token=kv_bytes_per_token(model, kv_dtype),
        attn_macs_per_ctx=2 * model["n_layers"] * model["n_heads"]
        * model["head_dim"])


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple:
    """(least time the chip could take, which bound sets it)."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
