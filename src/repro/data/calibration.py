"""Calibration capture: run the model eagerly, harvest per-layer input
moments for the scaling matrices (paper §2, App A.2: 256 calibration
samples).

The model zoo's ``linear`` records streaming CalibStats into ``ctx.tap``
whenever it is set — count, Σ|x|, Σx², Σxxᵀ per *named* projection. These
moments are sufficient for every scaling kind (identity / lqer /
qera-approx / qera-exact) without retaining activations, which is what
makes calibrating a 70B-class model feasible (the paper's scaling pass
dominates its pipeline cost; App A.4).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from repro.core.api import CalibStats, ptq_precision
from repro.data.synthetic import DataConfig, host_batch
from repro.models.linear import Ctx


def capture_calibration(
    params,
    model_cfg,
    data_cfg: DataConfig,
    forward_fn,
    n_batches: int = 4,
    need_autocorr: bool = True,
) -> Dict[str, CalibStats]:
    """Run ``n_batches`` calibration batches, returning per-layer stats.

    ``forward_fn(ctx, params, batch, cfg)`` is typically
    ``lambda ctx, p, b, c: lm_loss(ctx, p, b, c)`` — anything that routes
    activations through the linears.
    """
    tap: Dict[str, CalibStats] = {}
    ctx = Ctx(tap=tap)
    if not need_autocorr:
        # swap the recorder to skip the m×m moment
        orig_record = ctx.record

        def record(name, x, m):
            if name not in tap:
                tap[name] = CalibStats.init(m, need_autocorr=False)
            tap[name] = tap[name].update(x)
        ctx.record = record  # type: ignore[method-assign]
    with ptq_precision():
        for step in range(n_batches):
            batch = host_batch(data_cfg, step)
            forward_fn(ctx, params, batch, model_cfg)
    return tap


def calibration_summary(stats: Dict[str, CalibStats]) -> Dict[str, dict]:
    out = {}
    for name, s in stats.items():
        out[name] = {
            "count": float(s.count),
            "mean_abs": float(jax.numpy.mean(s.sum_abs / s.count)),
            "rms": float(jax.numpy.mean(
                jax.numpy.sqrt(s.sum_sq / s.count))),
            "has_autocorr": s.autocorr is not None,
        }
    return out
