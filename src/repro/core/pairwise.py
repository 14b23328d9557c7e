"""Vectorized pairwise residual-entropy scores — the ParaLiNGAM hot-spot.

For normalized rows ``xn: (p, n)`` with correlation matrix ``c: (p, p)``, the
residual of regressing ``x_i`` on ``x_j`` renormalized via paper Eq. (10) is

    u_ij = (x_i - c_ij * x_j) / sqrt(1 - c_ij^2)

The matrix ``HR[i, j] = H_hat(u_ij)`` holds every residual entropy *exactly
once*; the paper's messaging mechanism (Section 3.1) corresponds to forming

    I[i, j] = (Hx[j] - Hx[i]) + (HR[i, j] - HR[j, i])        (antisymmetric)
    S[i]    = sum_j  min(0, I[i, j])^2                        (masked)

so each unordered pair contributes to *both* workers' scores from one
computation. These functions are the pure-jnp oracle; the Pallas kernel in
``repro.kernels.pairwise_score`` computes HR with VMEM tiling.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.covariance import VAR_EPS, _sample_count
from repro.core.entropy import entropy_from_moments, log_cosh, u_exp_moment


def residual_entropy_block(xn, c_cols, xj, psum_axis: str | None = None,
                           n_valid=None, backend: str = "xla"):
    """HR block for all rows of ``xn: (p, n)`` against ``xj: (bj, n)`` with
    correlations ``c_cols: (p, bj)``. Returns (p, bj).

    ``psum_axis`` names a mesh axis the samples axis is sharded over (see
    :func:`stream_entropy`): the block math runs on the local n-shard and the
    moments are pmean'd before the entropy epilogue. ``n_valid`` as in
    :func:`stream_moments` (zero-padded sample columns). ``backend``
    ``"pallas"``/``"pallas_fused"`` computes the raw moment sums with the
    moments-emitting Pallas kernel (``kernels.ops.pairwise_moments``) and
    runs the same jnp finalize — because the kernel emits *sums*, not
    entropies, both seams (``psum_axis`` and ``n_valid``) compose with it
    unchanged (:func:`finalize_moments`)."""
    if backend in ("pallas", "pallas_fused"):
        from repro.kernels import ops as kops

        m1_sum, m2_sum = kops.pairwise_moments(xn, xj, c_cols)
        den = _sample_count(n_valid, xj.shape[-1])
        return finalize_moments(m1_sum, m2_sum, den, psum_axis=psum_axis)
    denom = jnp.sqrt(jnp.maximum(1.0 - jnp.square(c_cols), VAR_EPS))
    # u: (p, bj, n) — the big intermediate the Pallas kernel avoids spilling.
    u = (xn[:, None, :] - c_cols[:, :, None] * xj[None, :, :]) / denom[:, :, None]
    return stream_entropy(u, psum_axis=psum_axis, n_valid=n_valid)


def stream_moments(u, n_valid=None):
    """The two Hyvarinen moments of each length-n residual stream: per-stream
    means of ``log cosh u`` and ``u exp(-u^2/2)`` (reduce axis -1). Split out
    from :func:`stream_entropy` because the moments — unlike the entropy — are
    linear in the sample axis, which is what makes them *shardable*: equal
    sample shards can each reduce locally and ``pmean`` the results. A TPU
    kernel taking over this reduction must likewise expose (m1, m2), not H,
    so the cross-device combine stays a moment sum (``kernels/ops.py``).

    ``n_valid`` is the batched-fit padding seam: when the sample axis is
    zero-padded up to a shape bucket, both integrands vanish at the padded
    columns (``log cosh 0 = 0``, ``0 * exp(0) = 0`` — the residual streams of
    zero-padded samples are themselves exactly zero by the ``normalize``
    contract), so correcting the *denominator* to the traced valid count is
    sufficient to reproduce the unpadded moments."""
    if n_valid is None:
        m1 = jnp.mean(log_cosh(u), axis=-1)
        m2 = jnp.mean(u_exp_moment(u), axis=-1)
    else:
        den = _sample_count(n_valid, u.shape[-1])
        m1 = jnp.sum(log_cosh(u), axis=-1) / den
        m2 = jnp.sum(u_exp_moment(u), axis=-1) / den
    return m1, m2


def finalize_moments(m1_sum, m2_sum, den, psum_axis: str | None = None):
    """Entropy epilogue over raw moment *sums* — the finalize half of the
    moments-emitting kernel contract (``kernels/ops.py``).

    The Pallas kernels accumulate ``sum(log cosh u)`` / ``sum(u exp(-u^2/2))``
    over their sample tiles and emit the raw sums; this helper turns them into
    entropies: divide by the traced valid count ``den`` (the
    :func:`~repro.core.covariance._sample_count` contract — padded sample
    columns contribute zero to the sums, so the denominator alone carries the
    ``n_valid`` seam), optionally ``pmean`` across a sample-sharded mesh axis
    (each shard's sum/local-count is its local mean; equal shards make the
    pmean the global mean), then apply the nonlinear Hyvarinen formula. The
    nonlinearity stays out of the kernels precisely so this combine is legal.
    """
    m1 = m1_sum / den
    m2 = m2_sum / den
    if psum_axis is not None:
        m1 = jax.lax.pmean(m1, psum_axis)
        m2 = jax.lax.pmean(m2, psum_axis)
    return entropy_from_moments(m1, m2)


def stream_entropy(u, psum_axis: str | None = None, n_valid=None):
    """Hyvarinen entropy of each length-n residual stream (reduce axis -1).

    The single moment reduction every pairwise path shares: the square HR
    blocks, the fused triangular block pairs, the threshold scheduler's
    gathered chunks, and the ring bodies all feed their standardized residuals
    through here.

    With ``psum_axis`` set (inside ``shard_map``), ``u``'s trailing axis holds
    only this device's equal-size shard of the n samples: the local moments
    are ``pmean``'d over that mesh axis before the (nonlinear) entropy
    epilogue, which reproduces the full-sample moments exactly up to f32
    summation order — the ring's sample-sharding seam (dist/ring_order.py).
    ``n_valid`` as in :func:`stream_moments` (the padded-sample seam of the
    batched estimator frontend; the two seams are currently exclusive)."""
    m1, m2 = stream_moments(u, n_valid=n_valid)
    if psum_axis is not None:
        m1 = jax.lax.pmean(m1, psum_axis)
        m2 = jax.lax.pmean(m2, psum_axis)
    return entropy_from_moments(m1, m2)


def residual_entropy_block_pair(xi, c_blk, xj, n_valid=None):
    """Both-direction residual entropies for one (bi, bj) block pair.

    ``xi: (bi, n)``, ``xj: (bj, n)``, ``c_blk: (bi, bj)``. Returns
    ``(hr_fwd, hr_rev)`` with ``hr_fwd[a, b] = H(r_{x_a}^{(x_b)})`` and
    ``hr_rev[a, b] = H(r_{x_b}^{(x_a)})`` — one load of each block feeds both
    directions, the key reuse the fused triangular kernel is built around."""
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - jnp.square(c_blk), VAR_EPS))[..., None]
    u_f = (xi[:, None, :] - c_blk[..., None] * xj[None, :, :]) * inv
    u_r = (xj[None, :, :] - c_blk[..., None] * xi[:, None, :]) * inv
    return stream_entropy(u_f, n_valid=n_valid), stream_entropy(u_r, n_valid=n_valid)


def pair_moments(xn, c_vals, xj, n_valid=None, psum_axis: str | None = None):
    """Both-direction residual entropies for *gathered* comparison chunks.

    The threshold scheduler's per-round evaluation: worker rows ``xn: (m, n)``
    against their gathered chunk targets ``xj: (m, B, n)`` with correlations
    ``c_vals: (m, B)``. Returns ``(hr_fwd, hr_rev)``, each ``(m, B)``, with
    ``hr_fwd[w, b] = H(r_{x_w}^{(x_jb)})`` — like
    :func:`residual_entropy_block_pair` both directions come from one load of
    each stream (the messaging reuse), but the target axis is a gather, not a
    tile, so the layout stays XLA-native (see ``repro.kernels.ops``).

    ``psum_axis`` as in :func:`stream_entropy`: inside ``shard_map`` with the
    samples axis sharded over that mesh axis, each device's chunk holds only
    its n-shard and the Hyvarinen moments are pmean'd before the entropy
    epilogue — the seam that lets the threshold-in-ring state machine run on
    sample-sharded meshes."""
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - jnp.square(c_vals), VAR_EPS))[..., None]
    xi = xn[:, None, :]
    u_f = (xi - c_vals[..., None] * xj) * inv
    u_r = (xj - c_vals[..., None] * xi) * inv
    return (stream_entropy(u_f, psum_axis=psum_axis, n_valid=n_valid),
            stream_entropy(u_r, psum_axis=psum_axis, n_valid=n_valid))


def diag_block_scores(xb, c_diag, hxb, mb, n_valid=None):
    """Messaging-folded score contributions of the *diagonal* block tiles.

    ``xb: (nt, b, n)`` row blocks, ``c_diag: (nt, b, b)`` the matching
    diagonal correlation blocks, ``hxb: (nt, b)`` row entropies, ``mb:
    (nt, b)`` live mask. One HR block per tile covers both orderings of every
    in-block pair (the antisymmetric stat is ``hr - hr.T``), so only the
    row-sum credit applies — the column credit is the other ordering's row.
    Returns (nt, b) score contributions."""

    def one(x, cd, hx, m):
        hr = residual_entropy_block(x, cd, x, n_valid=n_valid)
        stat = pair_stat_matrix(hx, hr)
        pm = m[:, None] & m[None, :] & ~jnp.eye(x.shape[0], dtype=bool)
        return jnp.sum(jnp.where(pm, jnp.square(jnp.minimum(0.0, stat)), 0.0), axis=1)

    return jax.vmap(one)(xb, c_diag, hxb, mb)


def tri_block_maps(nt: int):
    """Static (numpy) tile maps of the strictly-lower triangular block grid:
    every unordered off-diagonal block pair (i < j) exactly once."""
    import numpy as np

    pairs = [(i, j) for i in range(nt) for j in range(i + 1, nt)]
    imap = np.asarray([ij[0] for ij in pairs], np.int32)
    jmap = np.asarray([ij[1] for ij in pairs], np.int32)
    return imap, jmap


def fused_layout(xn, c, mask, block: int, n_valid=None):
    """Shared prologue of the fused triangular sweep (jnp oracle and Pallas
    wrapper): pad p to the tile size, reshape into (nt, b) tiles and score
    the diagonal tiles. Returns ``(xpad, cp, c4, hxb, mb, s_diag)`` with
    ``xpad: (nt*b, n)``, ``cp: (nt*b, nt*b)`` the padded correlations,
    ``c4: (nt, nt, b, b)`` their tile view, ``hxb``/``mb``/``s_diag`` all
    (nt, b)."""
    p, n = xn.shape
    b = min(block, max(p, 1))
    p_pad = p + (-p) % b
    nt = p_pad // b
    xpad = jnp.pad(xn.astype(jnp.float32), ((0, p_pad - p), (0, 0)))
    mb = jnp.pad(mask, (0, p_pad - p)).reshape(nt, b)
    cp = jnp.pad(c.astype(jnp.float32), ((0, p_pad - p), (0, p_pad - p)))
    c4 = cp.reshape(nt, b, nt, b).transpose(0, 2, 1, 3)  # (nt, nt, b, b)
    hx = row_entropies(xn, mask, n_valid=n_valid)
    hxb = jnp.pad(hx.astype(jnp.float32), (0, p_pad - p)).reshape(nt, b)

    diag_idx = jnp.arange(nt)
    s_diag = diag_block_scores(
        xpad.reshape(nt, b, n), c4[diag_idx, diag_idx], hxb, mb, n_valid=n_valid
    )
    return xpad, cp, c4, hxb, mb, s_diag


@partial(jax.jit, static_argnames=("block", "unroll"))
def fused_scores(xn, c, mask, block: int = 32, unroll: bool = False,
                 n_valid=None):
    """Score vector S with no (p, p) HR round-trip — the jnp oracle of the
    fused triangular kernel (``repro.kernels.fused_score``).

    Triangular block sweep: each unordered (bi, bj) block pair is visited
    once; both residual-entropy directions are computed from the same loads,
    the antisymmetric stat and the messaging credit ``min(0, ±I)^2`` are
    applied immediately, and only per-block partial score vectors survive the
    sweep — the p x p intermediate is never formed. ``unroll=True`` replaces
    the lax.map with a python loop for dry-run cost extraction."""
    p, n = xn.shape
    xpad, _, c4, hxb, mb, s2 = fused_layout(xn, c, mask, block, n_valid=n_valid)
    nt, b = mb.shape
    p_pad = nt * b
    xb = xpad.reshape(nt, b, n)

    imap_np, jmap_np = tri_block_maps(nt)
    if len(imap_np):
        imap = jnp.asarray(imap_np)
        jmap = jnp.asarray(jmap_np)

        def pair_body(t):
            i, j = imap[t], jmap[t]
            hr_f, hr_r = residual_entropy_block_pair(
                xb[i], c4[i, j], xb[j], n_valid=n_valid
            )
            stat = (hxb[j][None, :] - hxb[i][:, None]) + (hr_f - hr_r)
            pm = mb[i][:, None] & mb[j][None, :]
            fwd = jnp.where(pm, jnp.square(jnp.minimum(0.0, stat)), 0.0)
            rev = jnp.where(pm, jnp.square(jnp.minimum(0.0, -stat)), 0.0)
            return jnp.sum(fwd, axis=1), jnp.sum(rev, axis=0)

        if unroll:
            parts = [pair_body(jnp.int32(t)) for t in range(len(imap_np))]
            f = jnp.stack([pq[0] for pq in parts])
            r = jnp.stack([pq[1] for pq in parts])
        else:
            f, r = jax.lax.map(pair_body, jnp.arange(len(imap_np)))
        s2 = s2.at[imap].add(f).at[jmap].add(r)

    s = s2.reshape(p_pad)[:p]
    return jnp.where(mask, s, jnp.inf)


@partial(jax.jit, static_argnames=("block_j", "unroll"))
def residual_entropy_matrix(xn, c, block_j: int = 32, unroll: bool = False,
                            n_valid=None):
    """Full HR: (p, p), computed in j-blocks to bound the (p, bj, n) buffer.

    A p that ``block_j`` does not divide gets zero columns up to the next
    multiple (a zero column with zero correlation is a finite residual); their
    entropies are sliced off, so the buffer stays (p, block_j, n) whatever p.
    ``unroll=True`` replaces the lax.map with a python loop — used by the
    dry-run cost extraction (XLA counts loop bodies once)."""
    p = xn.shape[0]
    block_j = min(block_j, p)
    p_pad = p + (-p) % block_j
    nb = p_pad // block_j
    xs, cs = xn, c
    if p_pad != p:
        xs = jnp.pad(xn, ((0, p_pad - p), (0, 0)))
        cs = jnp.pad(c, ((0, 0), (0, p_pad - p)))

    def one_block(jb):
        cols = jb * block_j + jnp.arange(block_j)
        xj = xs[cols]
        c_cols = cs[:, cols]
        return residual_entropy_block(xn, c_cols, xj, n_valid=n_valid)

    if unroll:
        blocks = jnp.stack([one_block(jnp.int32(i)) for i in range(nb)])
    else:
        blocks = jax.lax.map(one_block, jnp.arange(nb))  # (nb, p, bj)
    return jnp.transpose(blocks, (1, 0, 2)).reshape(p, p_pad)[:, :p]


def pair_stat_matrix(hx, hr):
    """Antisymmetric likelihood-ratio matrix I (paper Eq. 7)."""
    return (hx[None, :] - hx[:, None]) + (hr - hr.T)


def scores_from_stats(stat, mask):
    """S[i] = sum_j min(0, I_ij)^2 over live pairs; +inf for dead rows."""
    pair_mask = mask[:, None] & mask[None, :] & ~jnp.eye(stat.shape[0], dtype=bool)
    contrib = jnp.where(pair_mask, jnp.square(jnp.minimum(0.0, stat)), 0.0)
    s = jnp.sum(contrib, axis=1)
    return jnp.where(mask, s, jnp.inf)


def row_entropies(xn, mask, psum_axis: str | None = None, n_valid=None):
    """H_hat of each (already normalized) row. ``psum_axis`` as in
    :func:`stream_entropy` (rows hold local sample shards); ``n_valid`` as in
    :func:`stream_moments` (zero-padded sample columns)."""
    h = stream_entropy(xn, psum_axis=psum_axis, n_valid=n_valid)
    return jnp.where(mask, h, 0.0)


@partial(jax.jit, static_argnames=("block_j", "unroll"))
def dense_scores(xn, c, mask, block_j: int = 32, unroll: bool = False,
                 n_valid=None):
    """One-shot dense score vector (the TPU-natural 'Block Compare' analogue,
    with messaging folded in). Returns (S, I, HR)."""
    hx = row_entropies(xn, mask, n_valid=n_valid)
    hr = residual_entropy_matrix(xn, c, block_j=block_j, unroll=unroll,
                                 n_valid=n_valid)
    stat = pair_stat_matrix(hx, hr)
    return scores_from_stats(stat, mask), stat, hr
