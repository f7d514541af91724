"""Step builders: full training, QPEFT adapter training, microbatching.

Each builder returns a pure ``step(state, batch) -> (state, metrics)``
suitable for ``jax.jit`` with in/out shardings — the same function lowers
on a laptop mesh and on the 512-chip production mesh.

QPEFT (the paper's §4.4 training mode) keeps the quantized backbone in
``state.frozen`` with ``stop_gradient`` semantics (it is simply not
differentiated), trains only the adapter tree, and applies the per-rank
gradient scaling (Eq. 7/SGP, baked into ``gscale`` vectors) *before* the
optimizer — matching the paper's "attenuate updates along preserved
directions" rule under any optimizer.

Cross-pod int8 error-feedback gradient compression (beyond-paper, for the
DCN-bound regime) is exposed as ``compress_pods=True``: gradients are
averaged per pod by the normal SPMD all-reduce, then synced across pods
with an int8 psum inside shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import Ctx, lm_loss
from repro.optim import (
    AdamState,
    AdamW,
    apply_updates,
    clip_by_global_norm,
    ef_compressed_psum,
    scale_lr_grads_by_key,
)


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    step: jax.Array        # scalar int32


class QPEFTState(NamedTuple):
    trainable: Any         # adapter tree ({"l","r"} dicts)
    frozen: Any            # quantized backbone + norms + gscale vectors
    opt: AdamState
    step: jax.Array


def init_train_state(params: Any, opt: AdamW) -> TrainState:
    return TrainState(params=params, opt=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def init_qpeft_state(trainable: Any, frozen: Any, opt: AdamW) -> QPEFTState:
    return QPEFTState(trainable=trainable, frozen=frozen,
                      opt=opt.init(trainable),
                      step=jnp.zeros((), jnp.int32))


@dataclasses.dataclass(frozen=True)
class StepConfig:
    remat: str = "none"            # none | full
    grad_clip: float = 1.0
    compute_dtype: Any = jnp.bfloat16
    microbatch: int = 0            # 0 = no microbatching
    compress_pods: bool = False    # int8 EF all-reduce on the 'pod' axis
    mesh: Any = None               # enables activation sharding hints


def _grads_of(loss_fn: Callable, params: Any, batch: Dict,
              micro: int) -> Tuple[jax.Array, Any]:
    """(loss, grads), microbatched by scanning over batch slices."""
    if micro <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)
    b = batch["tokens"].shape[0]
    assert b % micro == 0, f"batch {b} not divisible by microbatch {micro}"
    mb = b // micro

    def slice_batch(i):
        return {k: jax.lax.dynamic_slice_in_dim(v, i * mb, mb, axis=0)
                for k, v in batch.items()}

    def body(carry, i):
        loss_acc, g_acc = carry
        li, gi = jax.value_and_grad(loss_fn)(params, slice_batch(i))
        g_acc = jax.tree_util.tree_map(lambda a, b_: a + b_, g_acc, gi)
        return (loss_acc + li, g_acc), None

    g0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), g0), jnp.arange(micro))
    scale = 1.0 / micro
    return loss * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)


def make_train_step(cfg: ModelConfig, opt: AdamW,
                    sc: StepConfig = StepConfig()) -> Callable:
    """Full-parameter LM training step."""
    # fused="off": training differentiates through every projection, and
    # the Pallas serving kernels define no VJP — keep the jnp lowering
    ctx = Ctx(compute_dtype=sc.compute_dtype, mesh=sc.mesh, fused="off")

    def loss_fn(params, batch):
        return lm_loss(ctx, params, batch, cfg, remat=sc.remat)

    def step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        loss, grads = _grads_of(loss_fn, state.params, batch, sc.microbatch)
        grads, gnorm = clip_by_global_norm(grads, sc.grad_clip)
        updates, opt_state = opt.update(grads, state.opt, state.params)
        params = apply_updates(state.params, updates)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "step": state.step + 1}
        return TrainState(params, opt_state, state.step + 1), metrics

    return step


def make_qpeft_step(cfg: ModelConfig, opt: AdamW,
                    sc: StepConfig = StepConfig()) -> Callable:
    """Adapter-only training on a frozen quantized backbone (§4.4)."""
    from repro.models.quantize import merge_qpeft, qpeft_grad_scales
    # fused="off": grads flow through the (l, r) adapters inside linear()
    ctx = Ctx(compute_dtype=sc.compute_dtype, mesh=sc.mesh, fused="off")

    def step(state: QPEFTState, batch: Dict) -> Tuple[QPEFTState, Dict]:
        frozen = state.frozen

        def loss_fn(trainable, b):
            params = merge_qpeft(trainable, frozen)
            return lm_loss(ctx, params, b, cfg, remat=sc.remat)

        loss, grads = _grads_of(loss_fn, state.trainable, batch,
                                sc.microbatch)
        # paper Eq. 7 / SGP: attenuate preserved-direction gradients
        scales = qpeft_grad_scales(state.trainable, frozen)
        grads = scale_lr_grads_by_key(grads, scales)
        grads, gnorm = clip_by_global_norm(grads, sc.grad_clip)
        updates, opt_state = opt.update(grads, state.opt, state.trainable)
        trainable = apply_updates(state.trainable, updates)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "step": state.step + 1}
        return QPEFTState(trainable, frozen, opt_state, state.step + 1), \
            metrics

    return step


# ==========================================================================
# Cross-pod compressed gradient sync (opt-in, shard_map over 'pod')
# ==========================================================================
def make_compressed_sync(mesh, specs: Any) -> Callable:
    """Returns sync(grads, ef) -> (synced, ef'): int8 EF psum over 'pod'.

    ``specs`` is a pytree of PartitionSpec matching the gradient tree,
    *without* the 'pod' axis (per-pod gradients are replicated across
    pods' corresponding shards before the sync). Used when per-pod
    gradients are produced independently and the cross-pod reduction
    should ride DCN compressed.
    """
    def sync(grads, ef):
        def inner(g, e):
            return ef_compressed_psum(g, e, axis="pod")
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(specs, specs),
            out_specs=(specs, specs),
            check_vma=False,
        )(grads, ef)

    return sync
