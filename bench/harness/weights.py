"""Seeded serving weights: the ``Q + LR`` containers, made on the device.

Serving speed depends on the shapes and dtypes of the containers, not on
what an SRR pass would have put in them, so the benchmark fills them from
``--seed`` instead of running PTQ. Every leaf is a pure function of
(seed, leaf path, layer index):

  * ``codes``  int8, uniform over [-3, 3] (the 3-bit MXINT range less
    its lone -4, so that the weight has mean zero);
  * ``scale``  f32 powers of two, one per 32-row block, set so that the
    dequantized weight has a standard deviation near 1/sqrt(rows)
    (``wo`` and ``down`` also divided by sqrt(2 x layers));
  * ``l``, ``r`` f32 rank-r factors whose product adds about a quarter of
    the backbone's output scale (the path a dropped correction would
    show in);
  * ``gscale`` ones (the QPEFT gradient scale; serving ignores it);
  * ``b``      qkv bias, small normal;
  * norm gains near 1, a unit-normal embedding, a 1/sqrt(d) LM head.

A stacked (scanned) leaf draws layer ``i`` from ``fold_in(key, i)`` at the
per-layer shape, so :mod:`reference` regenerates any one layer alone and
gets the same numbers the served model holds.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# codes of a zero-mean weight: the 3-bit MXINT range is [-4, 3], but a
# uniform draw over it has mean -1/2, a rank-one bias in every matrix
# that makes a deep random stack map every position to the same state
CODE_MIN, CODE_MAX = -3, 3
CODE_STD = 2.0                  # of the uniform draw over [-3, 3]
MX_BLOCK = 32
LR_OUT_SCALE = 0.25             # std of (x·L)·R against the backbone's ~1
STACKED_PREFIX = "groups."      # scanned layer stacks: leading dim = layer


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64 bits wide (two fold-ins of
    32 bits each, so seeds past 2**31 need no 64-bit mode)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(root: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(root, zlib.crc32(path.encode()))


def _std_rows(rows: int) -> float:
    return 1.0 / math.sqrt(rows)


def _branch_gain(parent: str, depth: int) -> float:
    """Output scale of a matrix: the last matrix of each residual branch
    (``wo``, ``down``) shrinks as 1/sqrt(2 x depth), as in the repo's own
    initializer, so the residual stream grows no faster than a sum of
    unit steps."""
    return 1.0 / math.sqrt(2 * depth) if parent in ("wo", "down") else 1.0


def make_leaf(key: jax.Array, path: str, shape: Tuple[int, ...],
              dtype, depth: int = 1) -> jax.Array:
    """One per-layer leaf (no stack dim) from its key and path; ``depth``
    is the number of layers in the stack."""
    name = path.rsplit(".", 1)[-1]
    parent = path.rsplit(".", 2)[-2] if path.count(".") >= 1 else ""
    f32 = jnp.float32
    if name == "codes":
        v = jax.random.randint(key, shape, CODE_MIN, CODE_MAX + 1, jnp.int32)
        return v.astype(jnp.int8)
    if name == "scale":
        rows = shape[-2] * MX_BLOCK
        std = _std_rows(rows) * _branch_gain(parent, depth)
        e0 = math.floor(math.log2(std / CODE_STD))
        bump = jax.random.bernoulli(key, 0.5, shape).astype(f32)
        return jnp.exp2(e0 + bump).astype(dtype)
    if name == "l":
        return (jax.random.normal(key, shape, f32)
                * _std_rows(shape[-2])).astype(dtype)
    if name == "r":
        rank = shape[-2]
        return (jax.random.normal(key, shape, f32)
                * (LR_OUT_SCALE * _branch_gain(parent, depth)
                   / math.sqrt(rank))).astype(dtype)
    if name == "gscale":
        return jnp.ones(shape, dtype)
    if name == "b":
        return (jax.random.normal(key, shape, f32) * 0.1).astype(dtype)
    if name == "g":
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if name == "w" and parent == "embed":
        return jax.random.normal(key, shape, f32).astype(dtype)
    if name == "w" and parent == "lm_head":
        return (jax.random.normal(key, shape, f32)
                * _std_rows(shape[0])).astype(dtype)
    raise ValueError(f"no seeded rule for leaf {path!r}")


def make_stacked_leaf(key: jax.Array, path: str, shape: Tuple[int, ...],
                      dtype) -> jax.Array:
    """A scanned leaf (leading dim = layer): layer i from fold_in(key, i)."""
    n = shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.vmap(lambda k: make_leaf(k, path, shape[1:], dtype, n))(keys)


def path_str(path) -> str:
    parts = []
    for e in path:
        parts.append(str(getattr(e, "key", getattr(e, "idx", e))))
    return ".".join(parts)


def leaf_specs(tree) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{dotted path: (shape, dtype name)} of an abstract or real tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {path_str(p): (tuple(x.shape), jnp.dtype(x.dtype).name)
            for p, x in flat}


def make_params(seed: int, abstract):
    """Fill ``abstract`` (a ShapeDtypeStruct tree) from the seed in one
    jitted call: every leaf is produced on the device in its served type."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [path_str(p) for p, _ in flat]
    specs = [(tuple(x.shape), x.dtype) for _, x in flat]

    def build(root):
        out = []
        for path, (shape, dtype) in zip(paths, specs):
            k = leaf_key(root, path)
            if path.startswith(STACKED_PREFIX):
                out.append(make_stacked_leaf(k, path, shape, dtype))
            else:
                out.append(make_leaf(k, path, shape, dtype))
        return out

    leaves = jax.jit(build)(root_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
