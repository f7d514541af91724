"""Model-level PTQ/QPEFT: fp param tree → Q + LR param tree.

This bridges the paper's per-matrix algorithm (repro.core) to the model
zoo's param-dict schema (repro.models.linear):

  {"w": (…, m, n)}  →  {"codes": int8, "scale": f32 (…, m/B, n),
                        "l": (…, m, r), "r": (…, r, n),
                        "gscale": (…, r) [, "b"]}

Stacked weights (scan groups: leading G dim; MoE experts: G, E dims) are
decomposed matrix-by-matrix over the leading indices — each (layer,
expert) gets its own k* split, exactly the paper's per-matrix rank
allocation. ``gscale`` carries the QPEFT per-rank gradient scale (Eq. 7
fixed-γ by default) so the training step needs no extra side state.

Policy: projection linears are quantized; embeddings, the LM head, norms
and modality projectors stay full-precision (matching the paper's
evaluated setting — transformer linears only).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (CalibStats, LayerReport, PTQConfig, ptq_precision,
                            quantize_layer)
from repro.core.qpeft import fixed_gamma_scale, sgp_scale
from repro.quant import MXIntQuantizer, make_quantizer
from repro.quant.mxint import pack_codes_4bit

EXCLUDE_NAMES = {"embed", "lm_head", "vision_proj", "frontend_proj"}

# tap-name role for each projection key (matches the names the model zoo
# passes to linear()); used to look up calibration stats
_ROLE = {
    "wq": "attn.wq", "wk": "attn.wk", "wv": "attn.wv", "wo": "attn.wo",
    "up": ".up", "gate": ".gate", "down": ".down",
    "router": "moe.router",
}


def _names(path) -> List[str]:
    return [str(getattr(e, "key", getattr(e, "idx", ""))) for e in path]


def _stats_for(stats: Optional[Dict[str, CalibStats]], names: List[str],
               layer_hint: str) -> Optional[CalibStats]:
    """Find calibration stats for a weight path: try the per-layer key
    (L<i>.<role>), then the pooled role key, then suffix match."""
    if not stats:
        return None
    leaf = names[-2] if names[-1] == "w" else names[-1]
    role = _ROLE.get(leaf, leaf)
    for key in (f"{layer_hint}{role}", role):
        if key in stats:
            return stats[key]
    for key in stats:
        if key.endswith(role) or key.endswith("." + leaf):
            return stats[key]
    return None


def _quantize_matrix(name: str, w, stats, cfg: PTQConfig, key,
                     container: str, recorder=None,
                     scaling=None) -> Tuple[Dict[str, jax.Array], LayerReport]:
    dec, rep = quantize_layer(name, w, stats, cfg, key, recorder=recorder,
                              scaling=scaling)
    qz = MXIntQuantizer(bits=cfg.quantizer.bits,
                        block_size=cfg.quantizer.block_size)
    packed = qz.quantize(dec.q)
    scale = jnp.exp2(packed.exponents.astype(jnp.float32))
    out: Dict[str, jax.Array] = {
        "scale": scale,
        "l": dec.l.astype(jnp.float32),
        "r": dec.r.astype(jnp.float32),
        "gscale": fixed_gamma_scale(dec.rank, dec.k, 0.1),
    }
    if container == "packed4":
        if cfg.quantizer.bits > 4:
            raise ValueError("packed4 container requires bits <= 4")
        out["packed"] = pack_codes_4bit(packed.codes)
    else:
        out["codes"] = packed.codes
    if recorder is not None:
        recorder.attach_container(name, out, container)
    return out, rep


def quantize_model_params(
    params: Any,
    stats: Optional[Dict[str, CalibStats]],
    cfg: PTQConfig,
    container: str = "int8",
    progress: Optional[Callable[[LayerReport], None]] = None,
    recorder=None,
) -> Tuple[Any, List[LayerReport]]:
    """Walk a model param tree, replacing each projection's fp weight with
    its SRR/QER decomposition. Pure host-side (offline calibration pass).

    ``recorder`` (duck-typed, see :mod:`repro.obs.quant`) captures a
    per-matrix quality record plus container byte accounting."""
    reports: List[LayerReport] = []
    root = jax.random.PRNGKey(cfg.seed)
    counter = [0]

    def visit(path, node):
        if not (isinstance(node, dict) and "w" in node
                and hasattr(node["w"], "ndim") and node["w"].ndim >= 2):
            return None  # not a linear params dict
        names = _names(path)
        if any(n in EXCLUDE_NAMES for n in names):
            return node
        w = np.asarray(node["w"], np.float32)
        lead = w.shape[:-2]
        name = "/".join(names)
        st = _stats_for(stats, names + ["w"], "")
        # one S for every matrix of a stack: they share the stats
        with ptq_precision():
            scaling = st.scaling(cfg.scaling) if st is not None else None

        def one(mat, idx):
            counter[0] += 1
            key = jax.random.fold_in(root, counter[0])
            q, rep = _quantize_matrix(f"{name}{list(idx)}", jnp.asarray(mat),
                                      st, cfg, key, container,
                                      recorder=recorder, scaling=scaling)
            reports.append(rep)
            if progress:
                progress(rep)
            return q

        if not lead:
            new = one(w, ())
        else:
            flat = w.reshape((-1,) + w.shape[-2:])
            qs = [one(flat[i], (i,)) for i in range(flat.shape[0])]
            new = {k: jnp.stack([q[k] for q in qs]).reshape(
                lead + qs[0][k].shape) for k in qs[0]}
        if "b" in node:
            new["b"] = node["b"]
        return new

    def walk(path, node):
        hit = visit(path, node)
        if hit is not None:
            return hit
        if isinstance(node, dict):
            return {k: walk(path + (jax.tree_util.DictKey(k),), v)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [walk(path + (jax.tree_util.SequenceKey(i),), v)
                    for i, v in enumerate(node)]
        if isinstance(node, tuple):
            return tuple(walk(path + (jax.tree_util.SequenceKey(i),), v)
                         for i, v in enumerate(node))
        return node

    return walk((), params), reports


# ==========================================================================
# Abstract (dry-run) variant: shapes only, no decomposition
# ==========================================================================
def quantized_abstract(params: Any, rank: int, block_size: int = 32,
                       container: str = "int8") -> Any:
    """ShapeDtypeStruct mirror of what quantize_model_params produces.

    Used by the dry-run to lower the serving path of a 32B model without
    ever materializing (or SVD-ing) its weights.
    """
    def visit(path, node):
        if not (isinstance(node, dict) and "w" in node
                and hasattr(node["w"], "ndim") and node["w"].ndim >= 2):
            return None
        names = _names(path)
        if any(n in EXCLUDE_NAMES for n in names):
            return node
        w = node["w"]
        lead, (m, n) = w.shape[:-2], w.shape[-2:]
        mpad = -(-m // block_size) * block_size  # MXINT row padding
        r = min(rank, min(m, n) // 2) if min(m, n) < 2 * rank else rank
        S = jax.ShapeDtypeStruct
        new = {
            "scale": S(lead + (mpad // block_size, n), jnp.float32),
            "l": S(lead + (m, r), jnp.float32),
            "r": S(lead + (r, n), jnp.float32),
            "gscale": S(lead + (r,), jnp.float32),
        }
        if container == "packed4":
            new["packed"] = S(lead + (mpad // 2, n), jnp.uint8)
        else:
            new["codes"] = S(lead + (mpad, n), jnp.int8)
        if "b" in node:
            new["b"] = node["b"]
        return new

    def walk(path, node):
        hit = visit(path, node)
        if hit is not None:
            return hit
        if isinstance(node, dict):
            return {k: walk(path + (jax.tree_util.DictKey(k),), v)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [walk(path + (jax.tree_util.SequenceKey(i),), v)
                    for i, v in enumerate(node)]
        if isinstance(node, tuple):
            return tuple(walk(path + (jax.tree_util.SequenceKey(i),), v)
                         for i, v in enumerate(node))
        return node

    return walk((), params)


# ==========================================================================
# QPEFT split / merge
# ==========================================================================
def _is_qlinear(node: Any) -> bool:
    return isinstance(node, dict) and ("codes" in node or "packed" in node)


def split_qpeft(qparams: Any) -> Tuple[Any, Any]:
    """(trainable, frozen): adapters {"l","r"} train; backbone freezes.

    Both trees keep the full nesting structure; the trainable tree holds
    ``None`` where nothing trains (dropped by jax as empty subtrees)."""
    def walk(node):
        if _is_qlinear(node):
            train = {"l": node["l"], "r": node["r"]}
            frozen = {k: v for k, v in node.items() if k not in ("l", "r")}
            return train, frozen
        if isinstance(node, dict):
            pairs = {k: walk(v) for k, v in node.items()}
            return ({k: t for k, (t, _) in pairs.items() if t is not None},
                    {k: f for k, (_, f) in pairs.items()})
        if isinstance(node, (list, tuple)):
            pairs = [walk(v) for v in node]
            t = type(node)(p[0] for p in pairs)
            f = type(node)(p[1] for p in pairs)
            return (t if any(p[0] is not None for p in pairs) else None), f
        return None, node

    t, f = walk(qparams)
    return t if t is not None else {}, f


def merge_qpeft(trainable: Any, frozen: Any) -> Any:
    """Inverse of split_qpeft."""
    def walk(t, f):
        if _is_qlinear(f):
            out = dict(f)
            if isinstance(t, dict):
                out.update(t)
            return out
        if isinstance(f, dict):
            return {k: walk(t.get(k) if isinstance(t, dict) else None, v)
                    for k, v in f.items()}
        if isinstance(f, (list, tuple)):
            ts = t if isinstance(t, (list, tuple)) else [None] * len(f)
            return type(f)(walk(ti, fi) for ti, fi in zip(ts, f))
        return f
    return walk(trainable, frozen)


def qpeft_grad_scales(trainable: Any, frozen: Any) -> Any:
    """Per-rank gradient-scale tree aligned with the trainable tree."""
    def walk(t, f):
        if isinstance(t, dict) and "l" in t and "r" in t and _is_qlinear(f):
            return {"gscale": f["gscale"]}
        if isinstance(t, dict):
            return {k: walk(v, f.get(k) if isinstance(f, dict) else None)
                    for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            fs = f if isinstance(f, (list, tuple)) else [None] * len(t)
            return type(t)(walk(ti, fi) for ti, fi in zip(t, fs))
        return None
    return walk(trainable, frozen)


def set_qpeft_scaling(qparams: Any, mode: str = "gamma", gamma: float = 0.1,
                      alpha: float = 5.0) -> Any:
    """Rebuild every gscale vector in a quantized tree (γ or SGP).

    Vectorized over leading (scan / expert) dims: the preserved-rank mask
    is recovered from the existing gscale (< 1 ⇔ preserved), so each
    stacked matrix keeps its own k*.
    """
    def walk(node):
        if _is_qlinear(node):
            out = dict(node)
            preserved = node["gscale"] < 1.0
            if mode == "gamma":
                g = jnp.where(preserved, gamma, 1.0)
            elif mode == "sgp":
                # rank-wise SGP (Eq. 8–9): σ_i from the R rows (R = ΣVᵀ)
                sigma = jnp.linalg.norm(node["r"], axis=-1)
                s_pres = jnp.where(preserved, sigma, 0.0)
                sigma1 = jnp.maximum(jnp.max(s_pres, axis=-1, keepdims=True),
                                     1e-12)
                lam = jnp.clip((alpha + 1.0) * sigma
                               / (alpha * sigma + sigma1), 0.0, 1.0)
                g = jnp.where(preserved, 1.0 - lam, 1.0)
            elif mode == "none":
                g = jnp.ones_like(node["gscale"])
            else:
                raise ValueError(mode)
            out["gscale"] = g.astype(jnp.float32)
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(qparams)
