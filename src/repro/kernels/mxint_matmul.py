"""Pallas TPU kernel: fused MXINT dequant-matmul with low-rank correction.

The QER/SRR serving hot spot is ``y = x·dequant(Q) + (x·L)·R``. A naive
XLA lowering materializes the dequantized f32/bf16 weight in HBM (2–4×
the quantized bytes) and runs the rank-r correction as a separate GEMM
with its own HBM round trip of the (M, N) output. This kernel instead:

  * streams int8 codes + per-32-block scales HBM→VMEM tile by tile and
    dequantizes *in VMEM* into an MXU-aligned (bk, bn) tile — the weight
    never exists in HBM at full precision, so the matmul's memory traffic
    is ~bits/16 of the bf16 baseline;
  * accumulates x @ W_tile in an f32 VMEM accumulator across the K grid;
  * fuses the low-rank correction on the **last K step**: ``xl = x·L``
    (an (M, r) sliver computed once outside — r ≤ 64 ≪ K so it is
    negligible) is multiplied by the (r, bn) slice of R straight into the
    same accumulator, saving a full (M, N) HBM round trip.

Tiling: bm×bn×bk = 128×128×512 by default — multiples of the 128×128 MXU;
bk a multiple of the MXINT block (32) so scale tiles align. VMEM per
step ≈ x(128·512·4) + codes(512·128) + scale(16·128·4) + out(128·128·4)
+ xl/r slivers ≈ 390 KiB ≪ 16 MiB v5e VMEM, leaving headroom for
double-buffering the HBM streams.

TPU adaptation note (DESIGN.md §3): the CUDA equivalents (e.g. LQER's
fused dequant GEMM) pivot on warp-level shuffles; here the same insight —
"dequantize in fast memory, fuse the correction" — maps to VMEM tiling +
MXU-aligned blocks instead.

Three entry points, all sharing the same tile geometry:

  * :func:`mxint_lowrank_matmul_2d`       — xl = x·L precomputed outside
    (one big fused GEMM for the sliver; best when N ≫ bn so xl is reused
    across many N blocks);
  * :func:`mxint_lowrank_matmul_fused_2d` — takes L itself and accumulates
    the (bm, r) sliver in a VMEM scratch across the K grid, applying ·R on
    the last K step: x never leaves VMEM between the backbone and the
    correction (single-pass decode shapes);
  * :func:`mxint_lowrank_matmul_batched_2d` — leading grid axis over a
    stack of G independent weights (scan groups / MoE expert dispatch):
    x (G, M, K) · codes (G, K, N), one pallas_call for the whole stack.

The 2d/fused entries also accept the **packed4** container (uint8, two
4-bit codes per byte, ``packed=True``): nibbles are unpacked in the
kernel body (:func:`_unpack_tile`), so the codes' HBM stream halves
again vs int8 — the container is never pre-expanded in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constraints import MXINT_BLOCK


def _check_tiles(m: int, k: int, n: int, bm: int, bk: int, bn: int,
                 mx_block: int) -> None:
    """The grid floor-divides every problem dim by its block; a ragged
    dim would silently drop the tail tile, so enforce the documented
    caller contract (ops.py pads before calling) with a loud error."""
    if m % bm or k % bk or n % bn:
        raise ValueError(
            f"mxint matmul tiles must divide the problem: (M={m}, K={k}, "
            f"N={n}) vs (bm={bm}, bk={bk}, bn={bn}) — pad to tile "
            f"multiples first (see kernels.ops._pad_to)")
    if bk % mx_block:
        raise ValueError(
            f"bk={bk} must be a multiple of the scale block {mx_block} "
            f"(canonically {MXINT_BLOCK}) so scale tiles align with "
            f"code tiles")


def _unpack_tile(packed: jax.Array) -> jax.Array:
    """packed4 (bk/2, bn) uint8 tile → int32 (bk, bn) codes, in VMEM.

    Row pairs interleave as [lo0, hi0, lo1, hi1, ...] — the layout
    :func:`repro.quant.mxint.pack_codes_4bit` writes — via a stack +
    reshape on the sublane axis (lane dim untouched, so Mosaic keeps the
    tile resident). Sign extension is shift-based (shl + arithmetic shr
    in the i32 working type, 2 ops/nibble) instead of a compare-select
    pair — this runs per (K, N) tile of every fused matmul and per
    (bs, hd) K/V tile of every int4 flash-decode step. The interleave
    stays in i32: Mosaic refuses the int8 sublane interleave when the
    lane dim is not a multiple of 128 (hd = 96), and every caller widens
    to f32 next anyway. Reading the packed container instead of
    pre-expanded int8 halves the codes' HBM stream."""
    u = packed.astype(jnp.int32)
    lo = (u << 28) >> 28                     # sign-extend 4-bit 2's comp
    hi = (u << 24) >> 28
    m2, bn = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(m2 * 2, bn)


def _dequant_tile(codes: jax.Array, scale: jax.Array,
                  mx_block: int, packed: bool = False) -> jax.Array:
    """Codes tile (int8, or packed4 uint8) + per-block scales → f32
    (bk, bn) weight tile."""
    if packed:
        codes = _unpack_tile(codes)
    codes = codes.astype(jnp.float32)
    bk, bn = codes.shape
    return (codes.reshape(bk // mx_block, mx_block, bn)
            * scale[:, None, :]).reshape(bk, bn)


def _kernel(x_ref, codes_ref, scale_ref, xl_ref, r_ref, o_ref, *,
            n_k: int, mx_block: int, packed: bool):
    """One (i, j, k) grid step: o[i,j] += x[i,k] @ dequant(codes[k,j])."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _dequant_tile(codes_ref[...], scale_ref[...], mx_block, packed)
    x = x_ref[...].astype(jnp.float32)                # (bm, bk)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _lowrank():
        xl = xl_ref[...].astype(jnp.float32)          # (bm, r)
        rr = r_ref[...].astype(jnp.float32)           # (r, bn)
        o_ref[...] += jnp.dot(xl, rr, preferred_element_type=jnp.float32)


def mxint_lowrank_matmul_2d(
    x: jax.Array,        # (M, K)
    codes: jax.Array,    # (K, N) int8, or packed4 (K/2, N) uint8
    scale: jax.Array,    # (K/32, N) f32
    xl: jax.Array,       # (M, r) — precomputed x @ L
    r: jax.Array,        # (r, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Core pallas_call; caller guarantees M % bm == K % bk == N % bn == 0
    and bk % mx_block == 0. ``packed`` reads the two-codes-per-byte
    container and unpacks nibbles in the kernel body."""
    m, k = x.shape
    n = codes.shape[1]
    mx_block = k // scale.shape[0]
    _check_tiles(m, k, n, bm, bk, bn, mx_block)
    rr = max(r.shape[0], 1)
    if r.shape[0] == 0:  # rank-0: keep the kernel uniform with a zero sliver
        xl = jnp.zeros((m, 1), x.dtype)
        r = jnp.zeros((1, n), x.dtype)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    cdiv = 2 if packed else 1    # packed rows hold two codes each

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, mx_block=mx_block,
                          packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // cdiv, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // mx_block, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, rr), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((rr, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, codes, scale, xl, r)


def _fused_kernel(x_ref, codes_ref, scale_ref, l_ref, r_ref, o_ref, xl_ref,
                  *, n_k: int, mx_block: int, packed: bool):
    """Like ``_kernel`` but builds the xl = x·L sliver *inside* the pass:
    each K step accumulates the (bm, r) partial into a VMEM scratch, and
    the last K step multiplies it with the (r, bn) slice of R. The sliver
    is recomputed per N block — r ≤ 64 keeps that rounding-error cheap
    relative to saving the separate (M, r) HBM round trip at decode."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        xl_ref[...] = jnp.zeros_like(xl_ref)

    x = x_ref[...].astype(jnp.float32)                # (bm, bk)
    w = _dequant_tile(codes_ref[...], scale_ref[...], mx_block, packed)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    xl_ref[...] += jnp.dot(x, l_ref[...].astype(jnp.float32),
                           preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _lowrank():
        rr = r_ref[...].astype(jnp.float32)           # (r, bn)
        o_ref[...] += jnp.dot(xl_ref[...], rr,
                              preferred_element_type=jnp.float32)


def mxint_lowrank_matmul_fused_2d(
    x: jax.Array,        # (M, K)
    codes: jax.Array,    # (K, N) int8, or packed4 (K/2, N) uint8
    scale: jax.Array,    # (K/32, N) f32
    l: jax.Array,        # (K, r)
    r: jax.Array,        # (r, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Single-pass y = x·dequant(Q) + (x·L)·R with the sliver accumulated
    in-kernel. Caller guarantees the same divisibility as the 2d entry;
    ``packed`` unpacks the two-codes-per-byte container in-kernel."""
    m, k = x.shape
    n = codes.shape[1]
    mx_block = k // scale.shape[0]
    _check_tiles(m, k, n, bm, bk, bn, mx_block)
    rr = max(r.shape[0], 1)
    if r.shape[0] == 0:
        l = jnp.zeros((k, 1), x.dtype)
        r = jnp.zeros((1, n), x.dtype)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    cdiv = 2 if packed else 1

    return pl.pallas_call(
        functools.partial(_fused_kernel, n_k=n_k, mx_block=mx_block,
                          packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // cdiv, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // mx_block, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, rr), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((rr, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, rr), jnp.float32)],
        interpret=interpret,
    )(x, codes, scale, l, r)


def _batched_kernel(x_ref, codes_ref, scale_ref, xl_ref, r_ref, o_ref, *,
                    n_k: int, mx_block: int):
    """One (g, i, j, k) grid step over a stack of G independent weights.
    Blocks carry a leading singleton G dim; ``ref[0]`` strips it."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _dequant_tile(codes_ref[0], scale_ref[0], mx_block)
    x = x_ref[0].astype(jnp.float32)                  # (bm, bk)
    o_ref[0] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _lowrank():
        xl = xl_ref[0].astype(jnp.float32)            # (bm, r)
        rr = r_ref[0].astype(jnp.float32)             # (r, bn)
        o_ref[0] += jnp.dot(xl, rr, preferred_element_type=jnp.float32)


def mxint_lowrank_matmul_batched_2d(
    x: jax.Array,        # (G, M, K)
    codes: jax.Array,    # (G, K, N) int8
    scale: jax.Array,    # (G, K/32, N) f32
    xl: jax.Array,       # (G, M, r) — precomputed x @ L per stack entry
    r: jax.Array,        # (G, r, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Stacked variant: grid leads with the G axis, so one pallas_call
    serves every expert / scanned layer in the stack (MoE dispatch)."""
    g, m, k = x.shape
    _, _, n = codes.shape
    mx_block = k // scale.shape[1]
    _check_tiles(m, k, n, bm, bk, bn, mx_block)
    rr = max(r.shape[1], 1)
    if r.shape[1] == 0:
        xl = jnp.zeros((g, m, 1), x.dtype)
        r = jnp.zeros((g, 1, n), x.dtype)
    n_k = k // bk
    grid = (g, m // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(_batched_kernel, n_k=n_k, mx_block=mx_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
            pl.BlockSpec((1, bk // mx_block, bn),
                         lambda gg, i, j, kk: (gg, kk, j)),
            pl.BlockSpec((1, bm, rr), lambda gg, i, j, kk: (gg, i, 0)),
            pl.BlockSpec((1, rr, bn), lambda gg, i, j, kk: (gg, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        interpret=interpret,
    )(x, codes, scale, xl, r)
