"""Normalization, covariance, and the paper's Eq. (10)/(11) rank-1 updates.

Math simplification (paper Section 3.4): with normalized rows,

  Eq. (10):  var(r_i^(j))            = 1 - cov(x_i, x_j)^2
  Eq. (11):  cov(r_i^root, r_j^root) = cov(x_i, x_j) - b_i * b_j
             with b_k = cov(x_k, x_root);
             renormalized:  C'[i,j] = (C[i,j] - b_i b_j) / (s_i s_j),
             s_k = sqrt(1 - b_k^2).

These let every iteration after the first run off the covariance matrix alone
(UpdateCovMat, Algorithm 8) plus a rank-1 data refresh (UpdateData,
Algorithm 7) — no per-pair sample regressions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Guard for 1 - cov^2 when |cov| -> 1 (numerically collinear variables).
VAR_EPS = 1e-12
# Floor used by the *iteration updates*: caps the per-iteration amplification
# of numerically collinear residuals at 1/sqrt(COLLINEAR_FLOOR) = 100x and is
# followed by an explicit renormalization (update_data), so drift cannot
# compound into overflow across the p iterations.
COLLINEAR_FLOOR = 1e-4


def _sample_count(n_valid, n: int, ddof: int = 0):
    """Effective sample count minus ``ddof`` as a traced float (>= 1).

    ``n_valid`` is the *batched-fit padding seam*: a traced scalar count of
    valid samples when the trailing sample axis is zero-padded up to a shape
    bucket (``None`` -> the static axis length, the unpadded fast path). Every
    function below that divides by a function of n routes the denominator
    through here so padded and unpadded datasets produce identical statistics.
    """
    if n_valid is None:
        return max(n - ddof, 1)
    return jnp.maximum(n_valid - ddof, 1).astype(jnp.float32)


def sample_mask(n: int, n_valid):
    """(n,) bool mask of valid sample columns (``None`` -> all valid)."""
    if n_valid is None:
        return None
    return jnp.arange(n) < n_valid


def scaled_std(centered, den, axis: int = -1):
    """Row standard deviation ``sqrt(max(sum(centered^2) / den, VAR_EPS))``
    without f32 overflow. Each row is first scaled by the power of two ``2^e``
    that brings its largest magnitude into [0.5, 1): raw rows can be huge
    (the raw SEM generator's reach ~1e20 at p=770, and their squares sum
    past the f32 range). Power-of-two scaling is exact, so wherever the unscaled
    formula does not overflow the results are the same bits. Returns
    ``(centered / 2^e, std / 2^e, e)``."""
    _, e = jnp.frexp(jnp.max(jnp.abs(centered), axis=axis, keepdims=True))
    scaled = jnp.ldexp(centered, -e)
    var = jnp.sum(jnp.square(scaled), axis=axis, keepdims=True) / den
    eps = jnp.ldexp(jnp.asarray(VAR_EPS, scaled.dtype), -2 * e)
    return scaled, jnp.sqrt(jnp.maximum(var, eps)), e


def normalize(x, axis: int = -1, ddof: int = 1, n_valid=None):
    """Standardize samples along ``axis`` (zero mean, unit adjusted variance).

    With ``n_valid`` set (requires ``axis=-1``), sample columns at index >=
    n_valid are treated as padding: means/variances divide by ``n_valid`` and
    the padded columns come back *exactly zero*, which makes the padding
    invisible to every downstream moment sum (see ``pairwise.stream_moments``).
    """
    mean_den = _sample_count(n_valid, x.shape[axis])
    smask = sample_mask(x.shape[-1], n_valid)
    if smask is None:
        mean = jnp.mean(x, axis=axis, keepdims=True)
        centered = x - mean
    else:
        assert axis in (-1, x.ndim - 1), "n_valid requires the sample axis last"
        xz = jnp.where(smask, x, 0.0)
        mean = jnp.sum(xz, axis=axis, keepdims=True) / mean_den
        centered = jnp.where(smask, x - mean, 0.0)
    scaled, std, _ = scaled_std(
        centered, _sample_count(n_valid, x.shape[axis], ddof), axis)
    return scaled / std


def cov_matrix(xn, ddof: int = 1, n_valid=None):
    """Covariance matrix of row-variables ``xn: (p, n)`` (normalized rows ->
    correlation matrix with unit diagonal). Zero-padded sample columns (the
    ``n_valid`` contract of :func:`normalize`) contribute nothing to the dot
    products, so only the denominator needs the true count. The product runs
    at full f32 precision: a TPU's default one-pass bf16 matmul would
    perturb every correlation, and with it every score."""
    return (jnp.matmul(xn, xn.T, precision=jax.lax.Precision.HIGHEST)
            / _sample_count(n_valid, xn.shape[-1], ddof))


def residual_std(cov_ij):
    """sqrt(var(r_i^(j))) = sqrt(1 - cov^2) per paper Eq. (10)."""
    return jnp.sqrt(jnp.maximum(1.0 - jnp.square(cov_ij), VAR_EPS))


def rank1_gates(b_raw, live):
    """The gated (b, s) pair both Eq. (10)/(11) rank-1 updates are built on:
    clipped regression coefficient and floored residual scale, with dead
    entries passing through unchanged (b = 0, s = 1). Shared by
    ``update_data``/``update_cov`` and the sharded re-implementation in
    ``dist/ring_order.py`` so the clip/floor semantics can never diverge."""
    b = jnp.where(live, jnp.clip(b_raw, -1.0, 1.0), 0.0)
    s = jnp.sqrt(jnp.maximum(1.0 - jnp.square(b), COLLINEAR_FLOOR))
    return b, s


def update_data(x, cov, root, mask, n_valid=None):
    """UpdateData (Algorithm 7): regress the root out of every remaining row
    and renormalize via Eq. (10). Fully vectorized rank-1 update.

    ``x: (p, n)`` normalized rows, ``cov: (p, p)``, ``root`` scalar index,
    ``mask: (p,) bool`` rows still in U (including the root before removal).
    Rows not in U (and the root row itself) are left untouched. ``n_valid``
    as in :func:`normalize` — zero-padded sample columns stay exactly zero
    through the rank-1 update, so only the renormalization denominator needs
    the true count.

    Eq. (10) renormalization is exact in infinite precision; in f32 the
    residual variance drifts from 1 over many iterations (and explodes for
    near-collinear pairs), so the Eq. (10) scale is floored and followed by
    an explicit sample renormalization — a mathematical no-op that keeps the
    invariant var(row) = 1 the rest of the algorithm relies on.
    """
    p, n = x.shape
    idx = jnp.arange(p)
    live = mask & (idx != root)
    b, s = rank1_gates(cov[:, root], live)
    x_root = x[root][None, :]
    out = (x - b[:, None] * x_root) / s[:, None]
    # drift correction (exact renormalization of live rows)
    var_den = _sample_count(n_valid, n, 1)
    var = jnp.sum(jnp.square(out), axis=1, keepdims=True) / var_den
    scale = jnp.where(live[:, None], jax.lax.rsqrt(jnp.maximum(var, VAR_EPS)), 1.0)
    return out * scale


def update_cov(cov, root, mask):
    """UpdateCovMat (Algorithm 8): Eq. (11) rank-1 covariance update with
    Eq. (10) renormalization. Entries involving removed rows are garbage by
    contract and masked by callers."""
    p = cov.shape[0]
    idx = jnp.arange(p)
    live = mask & (idx != root)
    b, s = rank1_gates(cov[:, root], live)
    new = (cov - jnp.outer(b, b)) / jnp.outer(s, s)
    # Correlations cannot exceed 1; clipping prevents drift compounding.
    new = jnp.clip(new, -1.0, 1.0)
    # Keep the diagonal exactly 1 for live rows (it is mathematically 1).
    eye = jnp.eye(p, dtype=bool)
    return jnp.where(eye, 1.0, new)
