"""Plain float32 forward of a dense decoder, written from its equations.

It reads nothing of the program: the model's sizes come from the
configuration file, and the weights are regenerated layer by layer from
the seed (:mod:`harness.weights`), dequantized into dense float32
matrices, under ``jax.default_matmul_precision("highest")``.

The block (pre-norm decoder):

    h  = rmsnorm(x) * g1                     (eps 1e-6)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)                  adjacent pairs (2i, 2i+1);
                                             "full": every dim, "half":
                                             the first hd/2 (ChatGLM 2D)
    x  = x + attn(q, k, v) Wo                causal, GQA: head h reads
                                             kv head h // (H / KV)
    h2 = rmsnorm(x) * g2
    x  = x + (silu(h2 Wgate) * (h2 Wup)) Wdown
    logits = (rmsnorm(x) * gf) Whead

The configuration serves with an int4 KV cache, so attention reads keys
and values as they are stored, chunk by chunk as the engine prefilled
them (:mod:`reference.common`).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.counts import cache_bytes
from reference.common import (Stack, attention, kv_store, layer_leaf,
                              qlr_specs, qlr_weight, rmsnorm, rope)

# configuration key -> the program's ModelConfig attribute
MODEL_KEYS = {"n_layers": "n_layers", "d_model": "d_model",
              "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
              "head_dim": "head_dim_", "d_ff": "d_ff", "vocab": "vocab",
              "qkv_bias": "qkv_bias", "rope": "rope_kind",
              "rope_theta": "rope_theta"}
# the last matrix of each residual branch
BRANCH_OUT = ("wo", "down")


# ---------------------------------------------------------------- layout
def linear_dims(model: Dict) -> Dict[str, tuple]:
    d, h, kv, hd, ff = (model["d_model"], model["n_heads"],
                        model["n_kv_heads"], model["head_dim"], model["d_ff"])
    return {"mixer.wq": (d, h * hd), "mixer.wk": (d, kv * hd),
            "mixer.wv": (d, kv * hd), "mixer.wo": (h * hd, d),
            "mlp.up": (d, ff), "mlp.gate": (d, ff), "mlp.down": (ff, d)}


def leaf_specs(model: Dict, weights: Dict) -> Dict[str, tuple]:
    """{path: (shape, dtype)} of the served parameter tree, derived from
    the configuration alone (the harness checks the program's tree
    against it before serving)."""
    L, d, v = model["n_layers"], model["d_model"], model["vocab"]
    out = {"embed.w": ((v, d), "float32"),
           "final_norm.g": ((d,), "float32"),
           "lm_head.w": ((d, v), "float32"),
           "groups.p0.norm1.g": ((L, d), "float32"),
           "groups.p0.norm2.g": ((L, d), "float32")}
    for name, (m, n) in linear_dims(model).items():
        base = f"groups.p0.{name}."
        out.update(qlr_specs(base, m, n, L, weights))
        if model["qkv_bias"] and name in ("mixer.wq", "mixer.wk",
                                          "mixer.wv"):
            out[base + "b"] = ((L, n), "float32")
    return out


# ------------------------------------------------------------ the work
def kv_bytes_per_token(model: Dict, kv_dtype: str) -> int:
    """K and V of one token, all layers, in the cache's storage type,
    with a scale per (layer, kv head) of each."""
    return cache_bytes(2 * model["n_layers"] * model["n_kv_heads"]
                       * model["head_dim"],
                       2 * model["n_layers"] * model["n_kv_heads"], kv_dtype)


def attn_macs_per_ctx(model: Dict) -> int:
    """Multiply-adds per (query, key) over all layers: q·k and p·v in
    every query head."""
    return 2 * model["n_layers"] * model["n_heads"] * model["head_dim"]


# ------------------------------------------------------------ the block
def layer(wts, x, pos0, cs, nvalid, past_k, past_v, past_valid,
          model: Dict):
    """One decoder block over one sequence's own rows x (N, d) (see
    :func:`reference.common.attention`). Returns the block's output and
    the rows' keys and values as the cache stores them."""
    n = x.shape[0]
    h_, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    pos = pos0 + jnp.arange(n)
    h = rmsnorm(x, wts["norm1"])

    def proj(name, inp):
        y = inp @ wts[name]
        return y + wts[name + ".b"] if name + ".b" in wts else y

    q = proj("mixer.wq", h).reshape(n, h_, hd)
    k = proj("mixer.wk", h).reshape(n, kvh, hd)
    v = proj("mixer.wv", h).reshape(n, kvh, hd)
    q = rope(q, pos, model["rope_theta"], model["rope"])
    k = rope(k, pos, model["rope_theta"], model["rope"])
    x = x + attention(q, k, v, pos0, cs, nvalid, past_k, past_v,
                      past_valid) @ wts["mixer.wo"]
    h2 = rmsnorm(x, wts["norm2"])
    mlp = (jax.nn.silu(h2 @ wts["mlp.gate"]) * (h2 @ wts["mlp.up"]))
    return x + mlp @ wts["mlp.down"], kv_store(k), kv_store(v)


class Reference(Stack):
    """The dense decoder of one configuration."""

    def __init__(self, model: Dict, weights: Dict):
        self.specs = leaf_specs(model, weights)
        super().__init__(model)

    def layer_weights(self, root, i):
        """Dense float32 weights of layer ``i`` (traced index)."""
        out = {}
        for name, (m, n) in linear_dims(self.model).items():
            base = f"groups.p0.{name}."
            out[name] = qlr_weight(root, i, self.specs, base, m, BRANCH_OUT)
            if base + "b" in self.specs:
                out[name + ".b"] = layer_leaf(root, base + "b", self.specs,
                                              i, BRANCH_OUT)
        for nm in ("norm1", "norm2"):
            out[nm] = layer_leaf(root, f"groups.p0.{nm}.g", self.specs, i,
                                 BRANCH_OUT)
        return out

    def layer(self, wts, x, pos0, cs, nvalid, past, past_valid):
        y, k, v = layer(wts, x, pos0, cs, nvalid, *past, past_valid,
                        self.model)
        return y, (k, v)

    def empty_past(self):
        m = self.model
        none = jnp.zeros((0, m["n_kv_heads"], m["head_dim"]), jnp.float32)
        return none, none
