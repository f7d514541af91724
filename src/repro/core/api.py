"""Model-level PTQ/QPEFT pipeline: walk named weights, apply SRR/QER.

This is the integration surface between the paper's per-matrix algorithm
and the framework: the trainer/server hand in a flat dict of named 2-D
weights plus per-layer calibration statistics; this module returns
decompositions + a report (k* per layer, errors, timings).

Calibration statistics are *streaming moments* (constant memory per layer):
count, Σ|x|, Σx², and optionally Σxxᵀ — enough to build every scaling kind
without retaining activations.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.qer import Decomposition, qer_decompose, scaled_error, w_only
from repro.core.scaling import (
    Scaling,
    autocorr_scaling_from_moments,
    identity_scaling,
)
from repro.core.srr import srr_decompose
from repro.quant import QuantizerConfig, make_quantizer

# The offline pass (calibration moments, the scaling eigh, the SVDs) runs
# its f32 matmuls at full precision: the TPU default is bf16 passes,
# which can shift the chosen k*. Serving never enters this scope.
PTQ_MATMUL_PRECISION = "highest"


def ptq_precision():
    """Context manager pinning the matmul precision of the offline pass."""
    return jax.default_matmul_precision(PTQ_MATMUL_PRECISION)


class CalibStats(NamedTuple):
    """Streaming per-layer input statistics. All in float32."""

    count: jax.Array    # scalar
    sum_abs: jax.Array  # (m,)
    sum_sq: jax.Array   # (m,)
    autocorr: Optional[jax.Array] = None  # (m, m) Σ xxᵀ

    @staticmethod
    def init(m: int, need_autocorr: bool = True) -> "CalibStats":
        return CalibStats(
            count=jnp.zeros((), jnp.float32),
            sum_abs=jnp.zeros((m,), jnp.float32),
            sum_sq=jnp.zeros((m,), jnp.float32),
            autocorr=jnp.zeros((m, m), jnp.float32) if need_autocorr else None,
        )

    def update(self, x: jax.Array) -> "CalibStats":
        """Accumulate a batch of activations x (..., m)."""
        x = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        ac = self.autocorr
        if ac is not None:
            ac = ac + x.T @ x
        return CalibStats(
            count=self.count + x.shape[0],
            sum_abs=self.sum_abs + jnp.sum(jnp.abs(x), axis=0),
            sum_sq=self.sum_sq + jnp.sum(x * x, axis=0),
            autocorr=ac,
        )

    def scaling(self, kind: str) -> Scaling:
        n = jnp.maximum(self.count, 1.0)
        if kind == "identity":
            return identity_scaling()
        if kind == "lqer":
            return Scaling(diag=jnp.maximum(self.sum_abs / n, 1e-6))
        if kind == "qera-approx":
            return Scaling(diag=jnp.maximum(jnp.sqrt(self.sum_sq / n), 1e-6))
        if kind == "qera-exact":
            if self.autocorr is None:
                raise ValueError("qera-exact needs autocorrelation moments")
            return autocorr_scaling_from_moments(self.autocorr / n)
        raise ValueError(f"unknown scaling kind {kind!r}")


@dataclasses.dataclass(frozen=True)
class PTQConfig:
    """One knob object for the whole offline pipeline."""

    method: str = "srr"             # srr | srr-joint | qer | w-only | none
    scaling: str = "qera-exact"     # see repro.core.scaling
    quantizer: QuantizerConfig = QuantizerConfig(kind="mxint", bits=3, block_size=32)
    rank: int = 64
    exact_svd: bool = False         # randomized SVD by default (paper A.4)
    seed: int = 0
    forced_k: Optional[int] = None  # override k* (ablations)

    def rank_for(self, shape: tuple[int, int]) -> int:
        """Effective budget for narrow matrices (e.g. MoE experts)."""
        return max(1, min(self.rank, min(shape) // 2))


class LayerReport(NamedTuple):
    name: str
    shape: tuple[int, int]
    rank: int
    k_star: int
    scaled_err: float
    weight_err: float
    seconds: float


def quantize_layer(
    name: str,
    w: jax.Array,
    stats: Optional[CalibStats],
    cfg: PTQConfig,
    key: jax.Array,
    quantizer=None,
    recorder=None,
    scaling: Optional[Scaling] = None,
) -> tuple[Decomposition, LayerReport]:
    """Apply the configured method to one weight matrix.

    ``scaling`` is S built from ``stats`` by the caller, so matrices that
    share one calibration stream share one eigendecomposition.

    ``recorder`` is an optional duck-typed observer (see
    :mod:`repro.obs.quant`) whose ``record_layer`` hook receives the
    inputs and results of every pass; this module never imports the
    observability package.
    """
    with ptq_precision():
        t0 = time.perf_counter()
        if scaling is None:
            scaling = (stats.scaling(cfg.scaling) if stats is not None
                       else identity_scaling())
        if quantizer is None:
            quantizer = make_quantizer(cfg.quantizer)
        rank = cfg.rank_for(w.shape)

        if cfg.method == "w-only":
            dec = w_only(w, quantizer, rank)
        elif cfg.method == "qer":
            dec = qer_decompose(w, scaling, quantizer, rank, key=key,
                                exact=cfg.exact_svd)
        elif cfg.method in ("srr", "srr-joint"):
            variant = "joint" if cfg.method == "srr-joint" else "split"
            res = srr_decompose(w, scaling, quantizer, rank, key,
                                k=cfg.forced_k, exact=cfg.exact_svd,
                                variant=variant)
            dec = res.decomposition
        elif cfg.method == "none":
            dec = Decomposition(
                q=w.astype(jnp.float32),
                l=jnp.zeros((w.shape[0], rank), jnp.float32),
                r=jnp.zeros((rank, w.shape[1]), jnp.float32), k=0)
        else:
            raise ValueError(f"unknown PTQ method {cfg.method!r}")

        serr = float(scaled_error(w, dec, scaling))
        werr = float(jnp.linalg.norm(w.astype(jnp.float32)
                                     - dec.reconstruct()))
        report = LayerReport(
            name=name, shape=tuple(w.shape), rank=rank, k_star=dec.k,
            scaled_err=serr, weight_err=werr,
            seconds=time.perf_counter() - t0,
        )
        if recorder is not None:
            recorder.record_layer(name, w, dec, scaling, cfg, quantizer,
                                  report)
    return dec, report


def quantize_tree(
    weights: Dict[str, jax.Array],
    stats: Dict[str, CalibStats],
    cfg: PTQConfig,
    progress: Optional[Callable[[LayerReport], None]] = None,
    recorder=None,
) -> tuple[Dict[str, Decomposition], list[LayerReport]]:
    """Quantize every named weight; deterministic per-layer PRNG streams."""
    root = jax.random.PRNGKey(cfg.seed)
    decs: Dict[str, Decomposition] = {}
    reports: list[LayerReport] = []
    for i, name in enumerate(sorted(weights)):
        key = jax.random.fold_in(root, i)
        dec, rep = quantize_layer(name, weights[name], stats.get(name), cfg, key,
                                  recorder=recorder)
        decs[name] = dec
        reports.append(rep)
        if progress is not None:
            progress(rep)
    return decs, reports


def report_summary(reports: list[LayerReport]) -> Dict[str, Any]:
    if not reports:
        return {}
    return {
        "layers": len(reports),
        "mean_scaled_err": float(jnp.mean(jnp.array([r.scaled_err for r in reports]))),
        "mean_weight_err": float(jnp.mean(jnp.array([r.weight_err for r in reports]))),
        "mean_k_star": float(jnp.mean(jnp.array([float(r.k_star) for r in reports]))),
        "total_seconds": sum(r.seconds for r in reports),
    }
