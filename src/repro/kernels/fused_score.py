"""Pallas TPU kernel: fused triangular score pipeline.

The square kernel (``pairwise_score.py``) emits the raw (m1, m2) moment sums
of HR[i, j] and HR[j, i] in *separate* grid tiles — every (x_i, x_j) block
pair is loaded from HBM twice — and leaves the antisymmetric stat and the
messaging credit to the jnp epilogue. This kernel fuses the whole score
pipeline:

  * **Triangular grid.** Tile t covers one unordered off-diagonal block pair
    (i < j) from a static tile map delivered by scalar prefetch (one packed
    int32 per tile); each (b, b) block pair is loaded exactly once. Tile count is nt(nt-1)/2 vs the square
    kernel's nt^2 (``tri_tile_count`` / ``square_tile_count`` below; the
    diagonal tiles are a vectorized jnp epilogue — O(p B n) work, a 1/nt
    fraction).
  * **Both directions per pass.** The same xi/xj/c loads feed the forward
    and reverse residual-entropy moments (4 VMEM raw-sum accumulators),
    halving HBM read traffic relative to the square grid.
  * **In-kernel scoring with a prefetched denominator.** The accumulators
    hold raw moment *sums* for the whole sample sweep; on the last sample
    block they are divided by a **scalar-prefetched valid count** (the
    ``n_valid`` seam — zero-padded samples contribute 0 to the sums, so the
    traced denominator alone corrects the statistics), then the entropy
    formula, the antisymmetric stat I and the messaging credit min(0, ±I)^2
    are applied in VMEM. Both endpoints' partial scores accumulate into a
    single resident (nt, 1, b) output — the kernel's HBM output shrinks from
    p^2 HR entries to the p score entries. This per-tile score contraction
    is why the fused kernel finalizes in-kernel (its output is p-sized, not
    p^2-sized); the square moments kernel is the one that exports raw sums
    for cross-device combining.
  * **Batched grid.** ``fused_score_batch`` prepends a dataset grid axis —
    grid (B, T, nk), every BlockSpec gains a leading batch index, and the
    prefetched valid-count vector is read at ``program_id(0)`` so each
    dataset in the bucket uses its own denominator. ``jax.vmap`` of the
    single-dataset entry lowers to the same leading-axis grid growth; both
    routes are parity-tested against each other and the oracle.

TPU layout: the chip's kernel compiler (Mosaic) takes a block only when its
last two dims are (8, 128)-aligned or the array's own, so the operands are
block-major — correlations ``(nt, nt, b, b)``, row entropies and masks
``(nt, 1, b)`` — and b stays a multiple of 8, BN of 128 (an O(p^2)
relayout per call against O(p^2 n) kernel work). The finalize flips
(b, 1) and (1, b) vectors with exact identity-mask reductions rather than
small transposes, and credits scores with ref stores on the output's
leading axis. Transcendental-bound -> VPU/EUP. The score output lives in
one VMEM-resident block for the whole grid, so tile order needs no
revisiting heuristics. Zero-padding of p and n is exact for the same reason as the
square kernel (padded samples contribute 0 to both moment sums; padded rows
carry mask 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.covariance import VAR_EPS, _sample_count
from repro.core.entropy import entropy_from_moments, log_cosh, u_exp_moment
from repro.core.pairwise import fused_layout, tri_block_maps
from repro.kernels.pairwise_score import require_tpu_tiling


def tri_tile_count(p: int, block: int) -> int:
    """Pair tiles the triangular grid visits (excluding the diagonal)."""
    nt = -(-p // block)
    return nt * (nt - 1) // 2


def square_tile_count(p: int, block: int) -> int:
    """Pair tiles the square HR grid visits for the same block size."""
    nt = -(-p // block)
    return nt * nt


#: Tiles of the triangular grid whose packed map fits scalar memory: SMEM is
#: 1 MiB on v5e and the map takes 4 bytes a tile (p=4096 at block 8 is
#: 130816 tiles).
MAX_TRI_TILES = 1 << 17


def tri_tile_map(nt: int):
    """Static triangular tile map, one int32 a tile: block row ``i`` in the
    high 16 bits, block column ``j`` in the low 16 (half the scalar memory
    of separate row and column maps)."""
    import numpy as np

    imap, jmap = tri_block_maps(nt)
    return (imap.astype(np.int32) << 16) | jmap.astype(np.int32)


def _tile_i(v):
    return v >> 16


def _tile_j(v):
    return v & 0xFFFF


def tri_block(p: int, block: int) -> int:
    """The row-block size the kernel runs at: ``block``, doubled until the
    triangular tile map fits ``MAX_TRI_TILES``."""
    while tri_tile_count(p, block) > MAX_TRI_TILES:
        block *= 2
    return block


def _sample_block(n: int, block_n: int) -> int:
    """Sample tile: ``block_n`` lanes (a multiple of 128), cut down to the
    128-rounded sample count so short streams are not padded to a full
    tile."""
    return min(block_n, n + (-n) % 128)


def _eye(b: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (b, b), 1))


# Exact (b, 1) <-> (1, b) flips: broadcast against an identity mask and
# reduce (x + 0.0 == x). They keep the finalize free of the small unaligned
# transposes the TPU kernel compiler refuses.
def _row_of_col(col):
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def _col_of_row(row):
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _fused_tri_kernel(nk: int, batched: bool, map_ref, den_ref,
                      xi_ref, xj_ref, c_ref, hxi_ref, hxj_ref, mi_ref, mj_ref,
                      s_ref, elc_f, exe_f, elc_r, exe_r):
    # Batched refs carry a leading size-1 dataset block; ``lead`` indexes it
    # away so one body serves both grids.
    lead = (0,) if batched else ()
    if batched:
        t = pl.program_id(1)
        k = pl.program_id(2)
        den = den_ref[pl.program_id(0)]
    else:
        t = pl.program_id(0)
        k = pl.program_id(1)
        den = den_ref[0]

    @pl.when(jnp.logical_and(t == 0, k == 0))
    def _init_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(k == 0)
    def _init_moments():
        elc_f[...] = jnp.zeros_like(elc_f)
        exe_f[...] = jnp.zeros_like(exe_f)
        elc_r[...] = jnp.zeros_like(elc_r)
        exe_r[...] = jnp.zeros_like(exe_r)

    xi = xi_ref[lead]  # (b, BN)
    xj = xj_ref[lead]
    cij = c_ref[lead + (0, 0)]  # (b, b) tile of the block-major correlations
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - cij * cij, VAR_EPS))[:, :, None]
    # Shared loads, both directions: u_f regresses x_i on x_j, u_r the
    # reverse — this is the half of the square kernel's HBM traffic.
    u_f = (xi[:, None, :] - cij[:, :, None] * xj[None, :, :]) * inv
    u_r = (xj[None, :, :] - cij[:, :, None] * xi[:, None, :]) * inv
    # Raw sums only; the prefetched denominator is applied at finalize.
    elc_f[...] += jnp.sum(log_cosh(u_f), axis=-1)
    exe_f[...] += jnp.sum(u_exp_moment(u_f), axis=-1)
    elc_r[...] += jnp.sum(log_cosh(u_r), axis=-1)
    exe_r[...] += jnp.sum(u_exp_moment(u_r), axis=-1)

    @pl.when(k == nk - 1)
    def _finalize():
        hr_f = entropy_from_moments(elc_f[...] / den, exe_f[...] / den)
        hr_r = entropy_from_moments(elc_r[...] / den, exe_r[...] / den)
        # Row entropies and masks arrive as (1, b) rows; the i side is
        # needed as a column.
        hxi = _col_of_row(hxi_ref[lead + (0,)])
        hxj = hxj_ref[lead + (0,)]
        mi = _col_of_row(mi_ref[lead + (0,)])
        mj = mj_ref[lead + (0,)]
        stat = (hxj - hxi) + (hr_f - hr_r)  # I[a, b], antisymmetric pairing
        # Select, not multiply: masked (dead/padded) rows may carry
        # non-finite garbage and 0 * NaN would leak it into live scores.
        pm = (mi * mj) > 0.5  # (b, b)
        fwd = jnp.sum(jnp.where(pm, jnp.square(jnp.minimum(0.0, stat)), 0.0),
                      axis=1, keepdims=True)  # (b, 1)
        rev = jnp.sum(jnp.where(pm, jnp.square(jnp.minimum(0.0, -stat)), 0.0),
                      axis=0, keepdims=True)  # (1, b)
        iv = _tile_i(map_ref[t])
        jv = _tile_j(map_ref[t])
        # Messaging: one evaluation credits both endpoints of the block pair.
        # Ref stores on the resident (nt, 1, b) score block, indexed on its
        # leading (untiled) axis.
        s_ref[lead + (pl.ds(iv, 1),)] += _row_of_col(fwd)[None]
        s_ref[lead + (pl.ds(jv, 1),)] += rev[None]


def _tri_call(xp, c4, hx3, m3, den, *, nt: int, b: int, block_n: int,
              interpret: bool):
    """One pallas_call of the triangular sweep. Operands are block-major so
    every BlockSpec's last two dims are full array dims or (8, 128)-aligned:
    ``xp: ([B,] nt*b, n_pad)``, ``c4: ([B,] nt, nt, b, b)``, ``hx3``/``m3``:
    ``([B,] nt, 1, b)``. A leading B axis selects the batched grid
    (B, T, nk) with one prefetched denominator per dataset."""
    from jax.experimental.pallas import tpu as pltpu

    lead = xp.shape[:-2]  # () or (B,)
    nk = xp.shape[-1] // block_n
    tile_map = tri_tile_map(nt)
    grid = lead + (len(tile_map), nk)
    if lead:
        def spec(shape, f):
            return pl.BlockSpec((1,) + shape,
                                lambda bi, t, k, tm, dn: (bi,) + f(t, k, tm))
    else:
        def spec(shape, f):
            return pl.BlockSpec(shape, lambda t, k, tm, dn: f(t, k, tm))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            spec((b, block_n), lambda t, k, tm: (_tile_i(tm[t]), k)),
            spec((b, block_n), lambda t, k, tm: (_tile_j(tm[t]), k)),
            spec((1, 1, b, b),
                 lambda t, k, tm: (_tile_i(tm[t]), _tile_j(tm[t]), 0, 0)),
            spec((1, 1, b), lambda t, k, tm: (_tile_i(tm[t]), 0, 0)),
            spec((1, 1, b), lambda t, k, tm: (_tile_j(tm[t]), 0, 0)),
            spec((1, 1, b), lambda t, k, tm: (_tile_i(tm[t]), 0, 0)),
            spec((1, 1, b), lambda t, k, tm: (_tile_j(tm[t]), 0, 0)),
        ],
        out_specs=spec((nt, 1, b), lambda t, k, tm: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((b, b), jnp.float32)] * 4,
    )
    return pl.pallas_call(
        functools.partial(_fused_tri_kernel, nk, bool(lead)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(lead + (nt, 1, b), jnp.float32),
        interpret=interpret,
    )(
        jnp.asarray(tile_map), den, xp, xp, c4, hx3, hx3, m3, m3,
    )


@functools.partial(
    jax.jit, static_argnames=("block", "block_n", "interpret")
)
def fused_score_vector(
    xn,
    c,
    mask,
    *,
    block: int = 8,
    block_n: int = 512,
    interpret: bool = False,
    n_valid=None,
):
    """Messaging-folded score vector S via the fused triangular kernel.

    ``xn: (p, n)`` normalized rows, ``c: (p, p)`` correlations, ``mask: (p,)``
    live rows. Returns (p,) float32 scores (+inf on dead rows) — identical
    math to ``dense_scores(...)[0]`` with no HR materialization. ``n_valid``
    (traced) is the batched-fit sample-padding seam: it rides into the kernel
    as a scalar-prefetch operand and only changes the finalize denominator."""
    p, n = xn.shape
    # Shared prologue with the jnp oracle: p-padding, (nt, b) tiling, row
    # entropies and the diagonal-tile epilogue (in-block pairs — tiny
    # relative to the off-diagonal sweep the kernel does).
    xpad, _, c4, hxb, mb, s2 = fused_layout(xn, c, mask, tri_block(p, block),
                                            n_valid=n_valid)
    nt, b = mb.shape
    block_n = _sample_block(n, block_n)
    if nt > 1:
        require_tpu_tiling(block=b, block_n=block_n)
        xp = jnp.pad(xpad, ((0, 0), (0, (-n) % block_n)))
        den = jnp.asarray(_sample_count(n_valid, n), jnp.float32).reshape(1)
        s_tri = _tri_call(
            xp, c4, hxb[:, None, :], mb.astype(jnp.float32)[:, None, :], den,
            nt=nt, b=b, block_n=block_n, interpret=interpret,
        )
        s2 = s2 + s_tri[:, 0, :]

    s = s2.reshape(nt * b)[:p]
    return jnp.where(mask, s, jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("block", "block_n", "interpret")
)
def fused_score_batch(
    xb,
    cb,
    maskb,
    *,
    block: int = 8,
    block_n: int = 512,
    interpret: bool = False,
    n_valid=None,
):
    """Batched fused score sweep on an explicit (B, T, nk) grid.

    ``xb: (B, p, n)`` normalized rows, ``cb: (B, p, p)``, ``maskb: (B, p)``,
    ``n_valid: (B,)`` per-dataset valid sample counts (or ``None`` when no
    dataset in the bucket is padded). Returns (B, p) float32 scores. The
    batch axis is the *leading grid axis*: one pallas_call covers the whole
    bucket, each dataset reading its own prefetched denominator at
    ``program_id(0)``. Semantically identical to ``jax.vmap`` of
    ``fused_score_vector`` (which lowers to the same leading-axis grid); the
    explicit form exists so the batched BlockSpec contract is concrete,
    benchmarkable (``bench_kernels.py`` ``batchkern_*`` lanes) and testable
    against both the vmap route and the jnp oracle."""
    bsz, p, n = xb.shape
    block = tri_block(p, block)
    if n_valid is None:
        layout = jax.vmap(
            lambda x, c, m: fused_layout(x, c, m, block)
        )(xb, cb, maskb)
    else:
        layout = jax.vmap(
            lambda x, c, m, nv: fused_layout(x, c, m, block, n_valid=nv)
        )(xb, cb, maskb, n_valid)
    xpad, _, c4, hxb, mb, s2 = layout
    nt, b = mb.shape[1:]
    block_n = _sample_block(n, block_n)
    if nt > 1:
        require_tpu_tiling(block=b, block_n=block_n)
        xp = jnp.pad(xpad, ((0, 0), (0, 0), (0, (-n) % block_n)))
        den = jnp.broadcast_to(
            jnp.asarray(_sample_count(n_valid, n), jnp.float32), (bsz,)
        )
        s_tri = _tri_call(
            xp, c4, hxb[:, :, None, :], mb.astype(jnp.float32)[:, :, None, :],
            den, nt=nt, b=b, block_n=block_n, interpret=interpret,
        )
        s2 = s2 + s_tri[:, :, 0, :]

    s = s2.reshape(bsz, nt * b)[:, :p]
    return jnp.where(maskb, s, jnp.inf)
