"""Activation-aware scaling matrices S for QER/SRR.

Each QER variant is defined by its choice of S (§2 of the paper):

  * ``identity``    — ZeroQuant-V2:     S = I
  * ``lqer``        — LQER:             S = diag(mean |x_j|)        (heuristic)
  * ``qera-approx`` — QERA-approx:      S = diag(sqrt(E[x_j²]))     (heuristic)
  * ``qera-exact``  — QERA-exact:       S = (E[x xᵀ])^{1/2}         (exact)

The exact variant minimizes the true output-space error
``E‖x(W − Ŵ)‖²`` since ``E‖xΔ‖² = ‖S Δ‖_F²`` with S the symmetric square
root of the input autocorrelation.

A :class:`Scaling` object exposes cheap ``apply``/``apply_inv`` so diagonal
scalings never materialize an m×m matrix.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

SCALING_KINDS = ("identity", "lqer", "qera-approx", "qera-exact")


@dataclasses.dataclass(frozen=True)
class Scaling:
    """S as either a diagonal vector or a dense symmetric matrix."""

    diag: Optional[jax.Array] = None       # (m,) — used when dense is None
    dense: Optional[jax.Array] = None      # (m, m)
    dense_inv: Optional[jax.Array] = None  # (m, m)

    @property
    def is_identity(self) -> bool:
        return self.diag is None and self.dense is None

    def apply(self, w: jax.Array) -> jax.Array:
        """S @ w."""
        if self.dense is not None:
            return self.dense @ w
        if self.diag is not None:
            return self.diag[:, None] * w
        return w

    def apply_inv(self, w: jax.Array) -> jax.Array:
        """S⁻¹ @ w."""
        if self.dense is not None:
            return self.dense_inv @ w
        if self.diag is not None:
            return w / self.diag[:, None]
        return w


def identity_scaling() -> Scaling:
    return Scaling()


def lqer_scaling(x: jax.Array, eps: float = 1e-6) -> Scaling:
    """diag of mean absolute activation per input channel. x: (N, m)."""
    d = jnp.mean(jnp.abs(x.astype(jnp.float32)), axis=0)
    return Scaling(diag=jnp.maximum(d, eps))


def qera_approx_scaling(x: jax.Array, eps: float = 1e-6) -> Scaling:
    """diag of root-mean-square activation per input channel."""
    d = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), axis=0))
    return Scaling(diag=jnp.maximum(d, eps))


def qera_exact_scaling(x: jax.Array, eps: float = 1e-4) -> Scaling:
    """Symmetric square root of the input autocorrelation E[x xᵀ].

    Computed via eigendecomposition so S and S⁻¹ share one factorization;
    eigenvalues are floored at ``eps·λ_max`` to keep S invertible (the
    paper requires invertible S).
    """
    x = x.astype(jnp.float32)
    return _sqrt_and_inverse((x.T @ x) / x.shape[0], eps)


def autocorr_scaling_from_moments(r: jax.Array, eps: float = 1e-4) -> Scaling:
    """qera-exact from a pre-accumulated autocorrelation matrix R = E[xxᵀ].

    This is the streaming-calibration entry point: the data pipeline
    accumulates ``Σ xxᵀ`` per layer across calibration batches (constant
    memory), then builds S once.
    """
    return _sqrt_and_inverse(r, eps)


def _sqrt_and_inverse(r: jax.Array, eps: float) -> Scaling:
    """S = R^{1/2} and S⁻¹ from one eigendecomposition of the symmetrized
    R, eigenvalues floored at ``eps·λ_max``.

    The eigendecomposition runs on the host (LAPACK, float64); the two
    products with the eigenvectors run on the default device. Eager
    only: this is an offline calibration step. On a TPU,
    ``jnp.linalg.eigh`` lowers to a QDWH divide-and-conquer program
    whose compile alone takes over 10 GB of host memory at 3072²."""
    r = np.asarray(r, np.float64)
    evals, evecs = np.linalg.eigh(0.5 * (r + r.T))
    evals = np.maximum(evals, eps * max(evals[-1], 1e-12))
    half = jnp.asarray(np.sqrt(evals), jnp.float32)
    evecs = jnp.asarray(evecs, jnp.float32)
    s = (evecs * half) @ evecs.T
    s_inv = (evecs / half) @ evecs.T
    return Scaling(dense=s, dense_inv=s_inv)


def make_scaling(kind: str, x: Optional[jax.Array] = None) -> Scaling:
    """Factory. ``x`` is the (N, m) calibration activation sample."""
    if kind == "identity":
        return identity_scaling()
    if x is None:
        raise ValueError(f"scaling kind {kind!r} needs calibration activations")
    if kind == "lqer":
        return lqer_scaling(x)
    if kind == "qera-approx":
        return qera_approx_scaling(x)
    if kind == "qera-exact":
        return qera_exact_scaling(x)
    raise ValueError(f"unknown scaling kind {kind!r}; options: {SCALING_KINDS}")
