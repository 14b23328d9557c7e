"""DirectLiNGAM step 2 in JAX: device-resident causal strengths B + noise
variances from a causal order.

Same closed form as the numpy oracle (``repro.core.pruning``): with the rows
in causal order, Sigma = A Omega A^T for the unit-lower-triangular
A = (I - B)^{-1}, so one jittered Cholesky + one unit-lower triangular solve
give B = I - A^{-1} and Omega = diag(L)^2. The point of the reimplementation
is *where* it runs: traced jnp ops mean the whole phase 2 fuses into the same
jit as the causal-order scan (``paralingam.fit``/``fit_batch``), so order + B
come out of a single device dispatch with no host round-trip between phases —
and the whole pipeline vmaps over a batch of datasets.

Two numerical deviations from the oracle, both documented here because they
are deliberate:

  * **correlation scaling** — the Cholesky runs on the correlation matrix R
    (rows pre-scaled by their sample std) rather than the raw covariance
    Sigma. Since Sigma = D R D for diagonal D, chol(Sigma) = D chol(R) and
    the unit-lower factors are related by the exact similarity
    A = D A_R D^{-1}; B and Omega are recovered by undoing the scaling. On
    the f32 device path this is materially better conditioned than
    factoring Sigma directly (SEM covariances span many decades of variance).
  * **jitter placement** — the oracle adds ``JITTER_SCALE * mean(var)`` to
    Sigma's diagonal; here ``JITTER_SCALE * mean(diag R)`` is added to R,
    i.e. the same relative ridge applied per-variable instead of uniformly.
    Both vanish at the 1e-10 scale; tests bound the difference.

Padding contracts (the batched-serve seam, shared with the scan driver):

  * ``mask`` marks live variable rows; padded (dead) rows must be zero in
    ``x`` and sit *after* all live entries in ``order`` (use
    :func:`complete_order` to sanitize a scan-driver order). Dead rows come
    back with zero B rows/columns and zero noise variance.
  * ``n_valid`` counts valid sample columns (``covariance.normalize``
    contract: padded columns zero).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.covariance import _sample_count, sample_mask, scaled_std
from repro.core.pruning import JITTER_SCALE


def complete_order(order, mask):
    """Extend a scan-driver causal order over a padded buffer into a full
    permutation of ``0..p-1``.

    The scan driver (``paralingam._scan_order_impl``) emits a (p,) order
    whose first ``sum(mask)`` entries are exactly the live variables, each
    once; positions past that hold garbage (there was no live row left to
    retire). The garbage entries are replaced by the dead variable ids (in
    ascending order), yielding a true permutation — the form the adjacency
    scatter and the gather ``x[order]`` need (duplicate indices would
    otherwise clobber live entries)."""
    p = order.shape[0]
    p_live = jnp.sum(mask)
    valid_pos = jnp.arange(p) < p_live
    # Variables named by the valid prefix (scatter-max so a garbage duplicate
    # in the tail can never un-mark a live one).
    seen = jnp.zeros((p,), bool).at[order].max(valid_pos)
    missing = jnp.nonzero(~seen, size=p, fill_value=0)[0].astype(order.dtype)
    take = jnp.clip(jnp.arange(p) - p_live, 0, p - 1)
    return jnp.where(valid_pos, order, missing[take])


def adjacency_from_order(x, order, mask=None, n_valid=None,
                         prune_below: float = 0.0):
    """B (p, p) and noise variances Omega (p,) from raw samples ``x: (p, n)``
    and a *permutation* ``order`` (see :func:`complete_order` for padded
    buffers). Fully traced — safe inside jit/vmap.

    Returns ``(b, omega)`` in original variable ids; optional hard threshold
    ``prune_below`` zeroes spurious small edges (static)."""
    p, n = x.shape
    order = order.astype(jnp.int32)
    xo = x[order]  # rows in causal order; padded rows (zeros) last

    # Centered covariance on the true sample count; padded columns stay 0.
    smask = sample_mask(n, n_valid)
    mean_den = _sample_count(n_valid, n)
    if smask is None:
        xc = xo - jnp.mean(xo, axis=1, keepdims=True)
    else:
        mu = jnp.sum(jnp.where(smask, xo, 0.0), axis=1, keepdims=True) / mean_den
        xc = jnp.where(smask, xo - mu, 0.0)
    cov_den = _sample_count(n_valid, n, 1)
    scaled, std_s, e = scaled_std(xc, cov_den, axis=1)
    xs = scaled / std_s
    std = jnp.ldexp(std_s, e)[:, 0]  # dead rows -> sqrt(VAR_EPS)
    # Full f32 precision (a TPU's default bf16 pass would perturb the
    # Cholesky factor of a near-singular correlation).
    corr = jnp.matmul(xs, xs.T, precision=jax.lax.Precision.HIGHEST) / cov_den

    p_live = p if mask is None else jnp.sum(mask)
    base = jnp.trace(corr) / jnp.maximum(p_live, 1)
    eye = jnp.eye(p, dtype=corr.dtype)

    # Jitter ladder: the oracle's 1e-10 ridge first (bit-comparable B on
    # well-conditioned problems), escalating only when the f32 factorization
    # actually breaks down (NaNs) — dense SEMs can put R's smallest eigenvalue
    # below f32 resolution, where *any* B on the near-null directions is
    # ill-determined and a visible ridge is the honest answer.
    chol = jnp.linalg.cholesky(corr + (JITTER_SCALE * base) * eye)
    for scale in (1e-6, 1e-4):
        retry = jnp.linalg.cholesky(corr + (scale * base) * eye)
        chol = jnp.where(jnp.isnan(chol).any(), retry, chol)
    a_r = chol / jnp.diagonal(chol)[None, :]  # unit lower triangular
    a_r_inv = jax.scipy.linalg.solve_triangular(
        a_r, jnp.eye(p, dtype=corr.dtype), lower=True, unit_diagonal=True
    )
    # Undo the std scaling: A = D A_R D^{-1}  =>  A^{-1} = D A_R^{-1} D^{-1}.
    b_ord = jnp.eye(p, dtype=corr.dtype) - a_r_inv * (std[:, None] / std[None, :])
    omega_ord = jnp.square(jnp.diagonal(chol) * std)
    if mask is not None:
        pos_live = jnp.arange(p) < p_live
        b_ord = jnp.where(pos_live[:, None] & pos_live[None, :], b_ord, 0.0)
        omega_ord = jnp.where(pos_live, omega_ord, 0.0)
    if prune_below > 0.0:
        b_ord = jnp.where(jnp.abs(b_ord) < prune_below, 0.0, b_ord)

    b = jnp.zeros_like(b_ord).at[order[:, None], order[None, :]].set(b_ord)
    omega = jnp.zeros((p,), b_ord.dtype).at[order].set(omega_ord)
    return b, omega


@partial(jax.jit, static_argnames=("prune_below",))
def estimate_adjacency(x, order, prune_below: float = 0.0):
    """Jitted standalone phase 2 (mirrors ``pruning.estimate_adjacency``'s
    signature for full, unpadded datasets). Returns B only; use
    :func:`adjacency_from_order` for (B, Omega) or padded buffers."""
    b, _ = adjacency_from_order(
        jnp.asarray(x), jnp.asarray(order, jnp.int32), prune_below=prune_below
    )
    return b


# Jitted (B, Omega) form — one fused executable instead of the op-by-op
# eager dispatch (the jitter ladder alone is three Cholesky launches).
# Callers that already trace (``paralingam._pipeline_impl``) use the plain
# function; standalone callers (``fit``'s ring branch) use this.
adjacency_from_order_jit = partial(
    jax.jit, static_argnames=("prune_below",)
)(adjacency_from_order)
