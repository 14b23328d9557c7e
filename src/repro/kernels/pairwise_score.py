"""Pallas TPU kernel: pairwise residual-entropy *moment* accumulator.

The ParaLiNGAM hot-spot. For every ordered pair (i, j) the scoring needs

    HR[i, j] = H_hat( (x_i - c_ij * x_j) / sqrt(1 - c_ij^2) )

whose only sample-axis reductions are the two Hyvarinen moments
``sum(log cosh u)`` and ``sum(u exp(-u^2/2))``. The kernel computes exactly
those raw *sums* — never the (p, p, n) residual tensor, never the entropy:
the grid is (p_i/BI, p_j/BJ, n/BN) with the sample dimension innermost, so
each (BI, BJ) tile streams sample blocks through VMEM and accumulates the
two moment sums in the resident output tiles. The nonlinear entropy formula,
the ``1/n`` (or ``1/n_valid``) mean and any cross-device moment combine are
a jnp epilogue (``pairwise.finalize_moments``) — emitting sums instead of
finished entropies is what makes the kernel compose with

  * the batched-fit ``n_valid`` seam: zero-padded sample columns contribute
    ``log_cosh(0) = 0`` and ``0 * exp(0) = 0`` to the sums, so the epilogue's
    traced denominator alone reproduces the unpadded statistics, and
  * the ring's sample sharding: each shard's kernel emits its local sums; the
    combine is a plain moment mean (``pmean``) *before* the nonlinearity —
    the ``psum_axis`` contract of ``pairwise.stream_moments``.

TPU considerations:
  * BN is a multiple of 128 (VPU lane width), BI a multiple of 8 (sublanes);
    the (BI, BJ) correlation/output tiles put BJ on lanes, so BJ is 128 or
    the whole (8-rounded) column count.
  * The workload is transcendental-heavy (log1p/exp) -> VPU-bound, no MXU
    use; arithmetic intensity grows with BI*BJ/(BI+BJ), so larger pair tiles
    directly buy HBM-bandwidth headroom (block-shape sweep in
    benchmarks/bench_kernels.py).
  * Zero-padding of p (to BI/BJ) and n (to BN) is exact for the same reason
    the ``n_valid`` seam is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.covariance import VAR_EPS, _sample_count
from repro.core.entropy import entropy_from_moments, log_cosh, u_exp_moment


def require_tpu_tiling(**blocks) -> None:
    """Refuse block sizes the TPU kernel compiler (Mosaic) would refuse, in
    interpret mode too, so a CPU parity test cannot pass at a tiling the
    chip rejects. Each name ending in ``_n`` is a lane block (a multiple of
    128); the rest are sublane blocks (a multiple of 8)."""
    for name, size in blocks.items():
        unit = 128 if name.endswith("_n") else 8
        if size % unit:
            raise ValueError(
                f"{name}={size} is not a multiple of {unit}: the TPU kernel "
                "compiler needs (8, 128)-aligned blocks")


def _pairwise_moments_kernel(nk, xi_ref, xj_ref, c_ref, m1_ref, m2_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        m1_ref[...] = jnp.zeros_like(m1_ref)
        m2_ref[...] = jnp.zeros_like(m2_ref)

    xi = xi_ref[...]  # (BI, BN)
    xj = xj_ref[...]  # (BJ, BN)
    cij = c_ref[...]  # (BI, BJ)
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - cij * cij, VAR_EPS))
    # u: (BI, BJ, BN)
    u = (xi[:, None, :] - cij[:, :, None] * xj[None, :, :]) * inv[:, :, None]
    # Raw sums only — the (BI, BJ) output tiles are VMEM-resident across the
    # innermost sample grid axis, so they double as the accumulators.
    m1_ref[...] += jnp.sum(log_cosh(u), axis=-1)
    m2_ref[...] += jnp.sum(u_exp_moment(u), axis=-1)


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "block_n", "interpret")
)
def pairwise_moments(
    xi,
    xj,
    c,
    *,
    block_i: int = 8,
    block_j: int = 128,
    block_n: int = 512,
    interpret: bool = False,
):
    """Raw Hyvarinen moment sums of every (i, j) residual stream.

    ``xi: (pi, n)`` row block, ``xj: (pj, n)`` column block (``xi is xj``
    for the full square), ``c: (pi, pj)`` their correlations. Returns
    ``(m1_sum, m2_sum)``, each (pi, pj) float32, with
    ``m1_sum[a, b] = sum_k log cosh u_ab[k]`` over the sample axis — no
    ``1/n``, no entropy. Finalize with ``pairwise.finalize_moments`` (which
    owns the ``n_valid`` denominator and the ``psum_axis`` combine).

    Mosaic wants the last two dims of every block (8, 128)-aligned or equal
    to the array's: a column block wider than the 8-rounded ``pj`` becomes
    that whole (padded) dim, and ``block_n`` shrinks to the 128-rounded
    sample count."""
    pi, n = xi.shape
    pj = xj.shape[0]
    block_j = min(block_j, pj + (-pj) % 8)
    block_n = min(block_n, n + (-n) % 128)
    require_tpu_tiling(block_i=block_i, block_n=block_n)
    if block_j % 128 and block_j != pj + (-pj) % 8:
        raise ValueError(
            f"block_j={block_j} must be a multiple of 128 or cover all "
            f"{pj} columns: it is the lane dim of the correlation tile")
    pi_pad = pi + (-pi) % block_i
    pj_pad = pj + (-pj) % block_j
    n_pad = n + (-n) % block_n
    xip = jnp.pad(xi.astype(jnp.float32), ((0, pi_pad - pi), (0, n_pad - n)))
    xjp = jnp.pad(xj.astype(jnp.float32), ((0, pj_pad - pj), (0, n_pad - n)))
    cc = jnp.pad(c.astype(jnp.float32), ((0, pi_pad - pi), (0, pj_pad - pj)))

    nk = n_pad // block_n
    grid = (pi_pad // block_i, pj_pad // block_j, nk)

    m1, m2 = pl.pallas_call(
        functools.partial(_pairwise_moments_kernel, nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, block_n), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_j, block_n), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_i, block_j), lambda i, j, k: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, block_j), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pi_pad, pj_pad), jnp.float32),
            jax.ShapeDtypeStruct((pi_pad, pj_pad), jnp.float32),
        ],
        interpret=interpret,
    )(xip, xjp, cc)
    return m1[:pi, :pj], m2[:pi, :pj]


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "block_n", "interpret")
)
def pairwise_score(
    xn,
    c,
    *,
    block_i: int = 8,
    block_j: int = 128,
    block_n: int = 512,
    interpret: bool = False,
    n_valid=None,
):
    """HR matrix via the moments kernel + jnp entropy epilogue. ``xn: (p, n)``
    normalized rows, ``c: (p, p)`` correlations. Returns (p, p) float32.
    ``n_valid`` (traced) is the batched-fit sample-padding seam — the kernel
    emits raw sums, so only the epilogue denominator changes."""
    m1_sum, m2_sum = pairwise_moments(
        xn, xn, c,
        block_i=block_i, block_j=block_j, block_n=block_n,
        interpret=interpret,
    )
    den = _sample_count(n_valid, xn.shape[-1])
    return entropy_from_moments(m1_sum / den, m2_sum / den)
