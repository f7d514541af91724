"""Pallas TPU kernel: flash-decode attention over the slot KV cache.

Why: after PR 2 every quantized projection runs fused, so serving decode
is dominated by the attention read over the cache. The XLA lowering
dequantized the whole int8 cache into f32 *and* (with the old
sequence-major layout) transposed it to bring the batch/head dims
adjacent before the score matmul — two full HBM round trips over the
largest live tensor, every token. This kernel reads the cache exactly
once, in its storage dtype:

  * single-query online-softmax attention, blocked along the sequence
    (slot) axis; running (m, l, acc) stats live in VMEM scratch across
    the S grid steps — the (G, S) score plane never touches HBM;
  * the cache is **head-major** ``(B, KV, S, hd)`` so each (batch, head)
    grid step streams a contiguous (bs, hd) tile — no transpose;
  * int8 KV dequantization is fused *inside*: codes stream HBM→VMEM as
    int8 (1 byte/elt) and the per-(slot, head) scale is applied to the
    (G, bs) score columns / probability columns instead of the (bs, hd)
    tile — the dense f32 cache never exists anywhere;
  * **int4 KV** reuses the packed4 nibble container: uint8 pages
    ``(B, KV, S/2, hd)`` hold two slots per byte (slot 2j = low nibble —
    the ``pack_codes_4bit`` layout, packed along the *slot* axis) and are
    unpacked in-kernel (:func:`~repro.kernels.mxint_matmul._unpack_tile`
    on the (bs/2, hd) tile), so codes stream HBM→VMEM at 0.5 byte/elt —
    the KV HBM footprint halves again vs int8;
  * per-row masking from explicit ``q_pos`` (B,) / ``k_pos`` (B, S)
    position maps — co-batched rows decode at unrelated positions
    (continuous batching) — plus an optional sliding window;
  * GQA via the (KV, G) head layout: one grid step scores all G query
    heads of a KV group against the group's single K/V stream.

Grid: (B, KV, S/bs) with the sequence axis innermost. VMEM per step ≈
k/v tiles (2·bs·hd·{1,4} B) + scores (G·bs·4) + acc (G·hd·4) ≪ 16 MiB
at bs = 256. Forward-only by design (serving needs no VJP).

Oracle: ``ref.decode_attention_ref``; dispatcher: ``ops.decode_attention_op``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constraints import PACKED4_SLOT_ALIGN, validate_page_size
from repro.kernels.mxint_matmul import _unpack_tile

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _lane_row(ref) -> jax.Array:
    """Load a ``(1, …, 1, n)`` block as one ``(1, n)`` lane row."""
    x = ref[...]
    return x.reshape(1, x.shape[-1])


def _decode_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, *rest,
                   n_s: int, window: int, scale: float, quantized: bool,
                   packed: bool):
    """``qp_ref`` is the scalar-prefetched (B,) position table in SMEM;
    ``kp_ref`` and the scale refs hold ``(…, 1, bs)`` blocks, so each
    block's second-minor dim equals its array's (the Mosaic tiling rule
    for a one-row block)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
    k = k_ref[0, 0]                                  # (bs, hd) / (bs/2, hd)
    if packed:
        # int4 KV: the (bs/2, hd) uint8 tile expands to (bs, hd) int8
        # codes in VMEM — slot pairs interleave on the sublane axis, the
        # layout pack_codes_4bit writes along the slot dim
        k = _unpack_tile(k)
    k = k.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    if quantized:
        # dequant fused on the (G, bs) score columns — G·bs multiplies
        # instead of bs·hd, and the f32 K tile never materializes
        s = s * _lane_row(ks_ref)
    s = s * scale

    qp = qp_ref[pl.program_id(0)]                    # scalar position
    kp = _lane_row(kp_ref)                           # (1, bs) slot positions
    mask = (kp >= 0) & (kp <= qp)
    if window > 0:
        mask = mask & (qp - kp < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # a lane whose running max is still NEG_INF has seen no valid slot
    # yet: exp(NEG_INF - NEG_INF) = 1 would credit every masked column
    # with unit probability, and a lane that stays empty through all S
    # blocks would emit an unweighted V-mean instead of zeros. Zero p
    # while m_new sits at the sentinel (real scores are bounded far
    # above NEG_INF/2); corr is then exp(0)·{l,acc}=0 — harmless.
    p = jnp.where(m_new > 0.5 * NEG_INF,
                  jnp.exp(s - m_new), 0.0)           # (G, bs)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    if quantized:
        p = p * _lane_row(vs_ref)                    # fold V scales into p
    v = v_ref[0, 0]                                  # (bs, hd) / (bs/2, hd)
    if packed:
        v = _unpack_tile(v)
    v = v.astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_prev * corr + pv

    @pl.when(si == n_s - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_decode_bkgd(
    q: jax.Array,              # (B, KV, G, hd)
    k: jax.Array,              # (B, KV, S, hd) — f32/bf16, int8 codes, or
                               # packed4 uint8 (B, KV, S/2, hd)
    v: jax.Array,              # same container as k
    q_pos: jax.Array,          # (B,) int32 per-row positions
    k_pos: jax.Array,          # (B, S) int32 per-(row, slot) map; -1 empty
    k_scale: jax.Array | None = None,   # (B, KV, S) f32 — int8/int4 KV only
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,           # 0 ⇒ no sliding window
    scale: float | None = None,
    bs: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Core pallas_call; S % bs == 0 is a hard contract (checked — a
    truncated tail would silently drop the newest cache slots). uint8
    ``k``/``v`` is the packed4 container: two slots per byte along the
    slot axis, unpacked in-kernel. Returns (B, KV, G, hd) in q.dtype."""
    b, kv, g, hd = q.shape
    packed = k.dtype == jnp.uint8
    s_len = k.shape[2] * (2 if packed else 1)
    if packed and k_scale is None:
        raise ValueError("packed4 (uint8) KV pages require k/v scales")
    bs = min(bs, s_len)
    if s_len % bs:
        raise ValueError(
            f"flash_decode_bkgd: S={s_len} is not a multiple of bs={bs} — "
            f"pad the slot axis (see ops._decode_attention_pallas) instead "
            f"of letting the grid drop the tail")
    if packed and bs % PACKED4_SLOT_ALIGN:
        raise ValueError(f"packed4 KV needs an even block, got bs={bs}")
    n_s = s_len // bs
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _decode_kernel, n_s=n_s, window=window, scale=float(scale),
        quantized=quantized, packed=packed)
    cdiv = 2 if packed else 1    # packed slot rows hold two codes each
    # q_pos rides in SMEM as a scalar-prefetch operand; k_pos and the
    # scales are viewed (…, n_s, 1, bs) so each one-row block spans its
    # array's second-minor dim, which compiled TPU requires
    in_specs = [
        pl.BlockSpec((1, 1, 1, bs), lambda bb, hh, ss, qp: (bb, ss, 0, 0)),
        pl.BlockSpec((1, 1, g, hd), lambda bb, hh, ss, qp: (bb, hh, 0, 0)),
        pl.BlockSpec((1, 1, bs // cdiv, hd),
                     lambda bb, hh, ss, qp: (bb, hh, ss, 0)),
        pl.BlockSpec((1, 1, bs // cdiv, hd),
                     lambda bb, hh, ss, qp: (bb, hh, ss, 0)),
    ]
    args = [q_pos.astype(jnp.int32),
            k_pos.astype(jnp.int32).reshape(b, n_s, 1, bs), q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, bs),
                                  lambda bb, hh, ss, qp: (bb, hh, ss, 0, 0))
                     ] * 2
        args += [s_.astype(jnp.float32).reshape(b, kv, n_s, 1, bs)
                 for s_ in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, n_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bb, hh, ss, qp: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),     # running max m
            pltpu.VMEM((g, 1), jnp.float32),     # running sum l
            pltpu.VMEM((g, hd), jnp.float32),    # running accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(*args)


def _paged_decode_kernel(bt_ref, *args, **kw):
    """Scalar-prefetch wrapper: the block table rode in as prefetch arg
    0 (it steered the index maps); the body is the shared flash-decode
    kernel, which reads only the position table (prefetch arg 1)."""
    del bt_ref
    _decode_kernel(*args, **kw)


def flash_decode_paged(
    q: jax.Array,              # (B, KV, G, hd)
    k: jax.Array,              # page pool (P, KV, ps, hd) — f32/bf16, int8
                               # codes, or packed4 uint8 (P, KV, ps/2, hd)
    v: jax.Array,              # same container as k
    q_pos: jax.Array,          # (B,) int32 per-row positions
    k_pos: jax.Array,          # (B, nb·ps) logical slot positions; -1 empty
    block_table: jax.Array,    # (B, nb) int32 physical page per block
    k_scale: jax.Array | None = None,   # (P, KV, ps) f32 — int8/int4 only
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash-decode: same online-softmax body as
    :func:`flash_decode_bkgd`, but the sequence grid axis walks each
    row's **block table** instead of a contiguous slot axis — the
    K/V/scale block specs are steered by a scalar-prefetched page-id
    table (``PrefetchScalarGridSpec``), so grid step (b, h, j) DMAs
    physical page ``block_table[b, j]`` and the pool never streams
    pages the row doesn't own. Every table entry must be a valid page id
    (the serving layer parks unused entries on a private page).

    The kernel block *is* the page: one page per sequence grid step. On
    real TPU hardware that means the page size must meet the Mosaic
    sublane tile (32 rows for int8/f32 pages, 64 logical slots for
    packed4); interpret mode — CPU validation — takes any even size.
    Returns (B, KV, G, hd) in q.dtype."""
    b, kv, g, hd = q.shape
    packed = k.dtype == jnp.uint8
    ps = k.shape[2] * (2 if packed else 1)
    nb = block_table.shape[1]
    if k_pos.shape[1] != nb * ps:
        raise ValueError(
            f"flash_decode_paged: k_pos covers {k_pos.shape[1]} slots but "
            f"the block table addresses {nb}×{ps}")
    if packed and k_scale is None:
        raise ValueError("packed4 (uint8) KV pages require k/v scales")
    # the kernel block IS the page: nibble pairs must land whole, and a
    # compiled (non-interpret) run must meet the Mosaic sublane tile —
    # fail at dispatch setup with the shared constraint error instead of
    # a Mosaic lowering crash
    validate_page_size(ps, packed=packed, strict=not interpret)
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _paged_decode_kernel, n_s=nb, window=window, scale=float(scale),
        quantized=quantized, packed=packed)
    cdiv = 2 if packed else 1
    # same operand views as flash_decode_bkgd, with the page as the block
    in_specs = [
        pl.BlockSpec((1, 1, 1, ps), lambda bb, hh, ss, bt, qp: (bb, ss, 0, 0)),
        pl.BlockSpec((1, 1, g, hd), lambda bb, hh, ss, bt, qp: (bb, hh, 0, 0)),
        pl.BlockSpec((1, 1, ps // cdiv, hd),
                     lambda bb, hh, ss, bt, qp: (bt[bb, ss], hh, 0, 0)),
        pl.BlockSpec((1, 1, ps // cdiv, hd),
                     lambda bb, hh, ss, bt, qp: (bt[bb, ss], hh, 0, 0)),
    ]
    args = [q_pos.astype(jnp.int32),
            k_pos.astype(jnp.int32).reshape(b, nb, 1, ps), q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, ps),
                                  lambda bb, hh, ss, bt, qp: (bt[bb, ss], hh, 0, 0))
                     ] * 2
        args += [s_.astype(jnp.float32)[:, :, None, :]
                 for s_ in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bb, hh, ss, bt, qp: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),     # running max m
            pltpu.VMEM((g, 1), jnp.float32),     # running sum l
            pltpu.VMEM((g, hd), jnp.float32),    # running accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), *args)
