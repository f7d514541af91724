"""What every architecture module of the reference shares.

An architecture module (``reference/<name>.py``, named by a
configuration's ``"reference"`` key; its contract is in
:mod:`harness.registry`) writes its block from these pieces:

  * the equations any decoder uses: :func:`rmsnorm`, :func:`rope`;
  * the int4 KV cache as the serving engine stores it: :func:`kv_store`,
    per (position, head), symmetric, ``scale = max|x| / 7``,
    ``codes = clip(round(x / scale), -7, 7)``;
  * :func:`attention`, which reads stored and fresh keys as the engine's
    chunked prefill does. A prompt position reads its own chunk's keys
    fresh and everything before the chunk from storage; a decoded
    position reads all of its context, itself included, from storage.
    Prompt positions below the request's prefix-cache hit ``m`` were
    prefilled by an earlier request in chunks that started at 0; the rest
    in chunks starting at ``m`` (:func:`chunk_starts`);
  * the served ``Q + LR`` containers: :func:`qlr_specs` (their layout)
    and :func:`qlr_weight` (layer ``i`` regenerated from the seed and
    dequantized as ``W = Q·s + L diag(g) R`` into a dense float32
    matrix, :mod:`harness.weights`);
  * :class:`Stack`, the run over a batch of requests: the first ``m``
    tokens of a request are a sequence of their own, the same for every
    request that shares them, so each shared prefix is computed once,
    layer by layer, and every request's own tokens (the rest of its
    prompt, then its served tokens) attend to that prefix's stored state,
    then to their own; :func:`pack` lays the requests out for it;
  * :func:`served_gaps`, the number the check compares.

It reads nothing of the program.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W

KV_QMAX = 7                     # int4 KV: symmetric codes in [-7, 7]
NORM_EPS = 1e-6
ROW_BLOCK = 256                 # query rows per attention block
PAD_TO = 1024                   # sequences pad to a multiple of this
SERVED_BLOCK = 64               # served tokens per request pad to this
QLR_KINDS = ("codes", "scale", "l", "r", "gscale")


# ---------------------------------------------------------------- layout
def qlr_specs(base: str, m: int, n: int, layers: int,
              weights: Dict) -> Dict[str, tuple]:
    """{path: (shape, dtype)} of one stacked ``Q + LR`` container of an
    (m, n) matrix under ``base`` (a path ending in '.')."""
    rank, blk = weights["rank"], weights["block"]
    mp = -(-m // blk) * blk
    r = min(rank, min(m, n) // 2) if min(m, n) < 2 * rank else rank
    return {base + "codes": ((layers, mp, n), "int8"),
            base + "scale": ((layers, mp // blk, n), "float32"),
            base + "l": ((layers, m, r), "float32"),
            base + "r": ((layers, r, n), "float32"),
            base + "gscale": ((layers, r), "float32")}


# --------------------------------------------------------------- weights
def layer_leaf(root, path: str, specs: Dict[str, tuple], i,
               branch_out: Tuple[str, ...]):
    """Layer ``i`` (traced index) of the stacked leaf at ``path``, as
    :func:`harness.weights.make_params` drew it."""
    shape, dt = specs[path]
    k = jax.random.fold_in(W.leaf_key(root, path), i)
    return W.make_leaf(k, path, shape[1:], jnp.dtype(dt), shape[0],
                       branch_out)


def qlr_weight(root, i, specs: Dict[str, tuple], base: str, m: int,
               branch_out: Tuple[str, ...]):
    """Dense float32 (m, n) matrix of layer ``i`` of the container at
    ``base``: ``Q·s + L diag(g) R``."""
    codes, scale, l, r, g = (layer_leaf(root, base + k, specs, i, branch_out)
                             for k in QLR_KINDS)
    n = codes.shape[-1]
    blk = codes.shape[0] // scale.shape[0]
    q = (codes.astype(jnp.float32).reshape(scale.shape[0], blk, n)
         * scale[:, None, :]).reshape(codes.shape)[:m]
    return q + (l * g[None, :]) @ r


# ------------------------------------------------------------ equations
def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * g


def rope(x, pos, theta: float, kind: str):
    """x (N, heads, hd); pos (N,). Rotates pairs (2i, 2i+1): "full" every
    dim, "half" the first hd/2 (ChatGLM's 2D rope), "none" none."""
    if kind == "none":
        return x
    hd = x.shape[-1]
    rot = hd if kind == "full" else hd // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (N, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                  -1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([y, x[..., rot:]], -1)


def kv_store(x):
    """What the int4 cache gives back for x (N, heads, hd)."""
    amax = jnp.max(jnp.abs(x), -1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / KV_QMAX
    return jnp.clip(jnp.round(x / scale), -KV_QMAX, KV_QMAX) * scale


def attention(q, k, v, pos0, cs, nvalid, past_k, past_v, past_valid):
    """q (N, H, hd); k, v (N, KV, hd) of N own rows at absolute positions
    pos0 + r, of which the first ``nvalid`` are real; cs (N,) absolute
    index of each query's first fresh key. past_k, past_v (P, KV, hd)
    hold, as stored, the keys and values of positions 0..P-1, of which
    the first ``past_valid`` are real (P may be 0). Head h reads kv head
    h // (H / KV)."""
    n, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    npast = past_k.shape[0]
    kf = jnp.concatenate([past_k, k])
    vf = jnp.concatenate([past_v, v])
    kq = jnp.concatenate([past_k, kv_store(k)])
    vq = jnp.concatenate([past_v, kv_store(v)])
    jpos = jnp.concatenate([jnp.arange(npast), pos0 + jnp.arange(n)])
    jreal = jnp.concatenate([jnp.arange(npast) < past_valid,
                             jnp.arange(n) < nvalid])
    q = q.reshape(n, kvh, g, hd) / math.sqrt(hd)

    def block(r0):
        qr = jax.lax.dynamic_slice_in_dim(q, r0, ROW_BLOCK, 0)
        csr = jax.lax.dynamic_slice_in_dim(cs, r0, ROW_BLOCK, 0)
        p = pos0 + r0 + jnp.arange(ROW_BLOCK)
        fresh = (jpos[None, :] >= csr[:, None])[None, None]   # (1,1,R,J)
        valid = (jreal[None, :] & (jpos[None, :] <= p[:, None]))[None, None]
        sf = jnp.einsum("rkgd,jkd->kgrj", qr, kf)
        sq = jnp.einsum("rkgd,jkd->kgrj", qr, kq)
        s = jnp.where(valid, jnp.where(fresh, sf, sq), -jnp.inf)
        s = s - jnp.max(s, -1, keepdims=True)
        e = jnp.where(valid, jnp.exp(s), 0.0)
        # a padding row with no key at all reads nothing
        pr = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
        o = (jnp.einsum("kgrj,jkd->rkgd", jnp.where(fresh, pr, 0.0), vf)
             + jnp.einsum("kgrj,jkd->rkgd", jnp.where(fresh, 0.0, pr), vq))
        return o.reshape(ROW_BLOCK, h * hd)

    out = jax.lax.map(block, jnp.arange(0, n, ROW_BLOCK))
    return out.reshape(n, h * hd)


# ------------------------------------------------------------- the run
class Stack:
    """The reference over a batch of requests, jitted in three pieces:
    the embedding, one layer, the head. An architecture's ``Reference``
    subclasses it and supplies

      * ``specs``: its :func:`leaf_specs` of the configuration;
      * ``layer_weights(root, i)``: layer ``i``'s weights, regenerated;
      * ``layer(wts, x, pos0, cs, nvalid, past, past_valid)``: one block
        over one sequence's own rows x (N, d) (see :func:`attention`),
        returning its output and the rows' state as the cache stores it
        (a tuple of arrays with a leading position axis), for a later
        sequence to read as ``past``;
      * ``empty_past()``: that state for no positions.

    The embedding (``embed.w``), final norm (``final_norm.g``) and head
    (``lm_head.w``) are read by those paths."""

    specs: Dict[str, tuple]

    def __init__(self, model: Dict):
        self.model = model

        def embed(root, tokens):
            shape, dt = self.specs["embed.w"]
            e = W.make_leaf(W.leaf_key(root, "embed.w"), "embed.w", shape,
                            jnp.dtype(dt), 1, ())
            return e[tokens]

        def one_layer(root, i, pre, req):
            """Layer ``i`` over every shared prefix, then over every
            request's own rows, reading its prefix's stored state."""
            wts = self.layer_weights(root, i)
            none = self.empty_past()

            def prefix(a):
                x, cs, nv = a
                return self.layer(wts, x, 0, cs, nv, none, 0)

            x_pre, stored = jax.lax.map(
                prefix, (pre["x"], pre["cs"], pre["nvalid"]))

            def own(a):
                x, pos0, cs, nv, grp = a
                past = tuple(s[grp] for s in stored)
                return self.layer(wts, x, pos0, cs, nv, past, pos0)[0]

            x_req = jax.lax.map(own, (req["x"], req["pos0"], req["cs"],
                                      req["nvalid"], req["group"]))
            return dict(pre, x=x_pre), dict(req, x=x_req)

        def head(root, x):
            gs, gdt = self.specs["final_norm.g"]
            hs, hdt = self.specs["lm_head.w"]
            g = W.make_leaf(W.leaf_key(root, "final_norm.g"), "final_norm.g",
                            gs, jnp.dtype(gdt), 1, ())
            wh = W.make_leaf(W.leaf_key(root, "lm_head.w"), "lm_head.w", hs,
                             jnp.dtype(hdt), 1, ())
            return rmsnorm(x, g) @ wh

        self._embed = jax.jit(embed)
        self._layer = jax.jit(one_layer)
        self._head = jax.jit(head)

    def served_logits(self, seed: int, seqs: Sequence[Dict], batch: int = 0,
                      groups: int = 0) -> List[np.ndarray]:
        """For each request ``{"prompt", "served", "matched", "chunk"}``
        the reference's logits (len(served), vocab) at the positions that
        produced its served tokens. Requests pad to ``batch`` rows and
        shared prefixes to ``groups``, lengths to whole blocks, so that
        runs reuse compiled shapes."""
        pre, req, rows = pack(seqs, batch, groups)
        root = W.root_key(seed)
        with jax.default_matmul_precision("highest"):
            pre, req = ({"x" if k == "tokens" else k:
                         self._embed(root, v) if k == "tokens" else v
                         for k, v in d.items()} for d in (pre, req))
            for i in range(self.model["n_layers"]):
                pre, req = self._layer(root, jnp.int32(i), pre, req)
            sel = jnp.take_along_axis(req["x"], jnp.asarray(rows)[..., None],
                                      1)
            logits = np.asarray(self._head(root, sel))
        return [logits[i, :len(s["served"])] for i, s in enumerate(seqs)]


def _round_up(n: int, block: int) -> int:
    return max(block, -(-n // block) * block)


def pack(seqs: Sequence[Dict], batch: int = 0, groups: int = 0):
    """Arrays for :meth:`Stack.served_logits`: the distinct shared
    prefixes (each request's first ``matched`` prompt tokens, with its
    chunk width), each request's own rows, and the row of each served
    token's logits."""
    keys, grp = {}, []
    for s in seqs:
        m = int(s["matched"])
        if not 0 <= m < len(s["prompt"]):
            raise ValueError(f"prefix hit {m} of a {len(s['prompt'])}-token "
                             f"prompt")
        key = (np.asarray(s["prompt"][:m], np.int32).tobytes(), s["chunk"])
        grp.append(keys.setdefault(key, len(keys)))
    prefixes = [(np.frombuffer(t, np.int32), c) for t, c in keys]
    npre = _round_up(max(len(t) for t, _ in prefixes), PAD_TO)
    g = max(groups, len(prefixes))
    pre = {"tokens": np.zeros((g, npre), np.int32),
           "cs": np.zeros((g, npre), np.int32),
           "nvalid": np.zeros((g,), np.int32)}
    for i, (t, chunk) in enumerate(prefixes):
        pre["tokens"][i, :len(t)] = t
        pre["cs"][i] = chunk_starts(npre, npre, npre, chunk)
        pre["nvalid"][i] = len(t)
    own = [np.concatenate([np.asarray(s["prompt"][s["matched"]:], np.int32),
                           np.asarray(s["served"][:-1], np.int32)])
           for s in seqs]
    nown = _round_up(max(len(o) for o in own), ROW_BLOCK)
    b = max(batch, len(seqs))
    # served tokens per request pad to whole blocks too: each new count
    # would compile the head again
    t = _round_up(max(len(s["served"]) for s in seqs), SERVED_BLOCK)
    req = {"tokens": np.zeros((b, nown), np.int32),
           "pos0": np.zeros((b,), np.int32),
           "cs": np.zeros((b, nown), np.int32),
           "nvalid": np.ones((b,), np.int32),
           "group": np.zeros((b,), np.int32)}
    rows = np.zeros((b, t), np.int32)
    for i, (s, o) in enumerate(zip(seqs, own)):
        m, eff = int(s["matched"]), len(s["prompt"])
        req["tokens"][i, :len(o)] = o
        req["pos0"][i] = m
        req["nvalid"][i] = len(o)
        req["group"][i] = grp[i]
        req["cs"][i] = chunk_starts(m + nown, eff, m, s["chunk"])[m:]
        r = eff - 1 - m + np.arange(len(s["served"]))
        rows[i, :len(r)] = r
        rows[i, len(r):] = r[-1]
    return pre, req, rows


def chunk_starts(n: int, eff: int, matched: int, chunk: int) -> np.ndarray:
    """First fresh key index of every position of one sequence (see the
    module docstring); decoded positions (>= eff) read nothing fresh."""
    p = np.arange(n)
    cs = np.where(p < matched, (p // chunk) * chunk,
                  matched + ((p - matched) // chunk) * chunk)
    return np.where(p >= eff, p + 1, cs).astype(np.int32)


def served_gaps(ref: Stack, seed: int, seqs: Sequence[Dict],
                batch: int = 0, groups: int = 0) -> List[np.ndarray]:
    """For each request: the gap by which each served token's logit lies
    below the reference's best at its position."""
    gaps = []
    for s, lg in zip(seqs, ref.served_logits(seed, seqs, batch, groups)):
        served = np.asarray(s["served"], np.int64)
        gaps.append(lg.max(-1) - lg[np.arange(len(served)), served])
    return gaps
