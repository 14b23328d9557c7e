"""ParaLiNGAM's worker decomposition as a ``shard_map`` ppermute ring.

The paper assigns each of the p "workers" (variables) to a CUDA thread block;
here each *device* owns a contiguous block of rows of the normalized data
``xn: (p, n)`` and the corresponding rows of the correlation matrix
``c: (p, p)``. Root-finding needs, for every live unordered pair (i, j), the
antisymmetric statistic (paper Eq. 7, via pairwise.py)

    I[i, j] = (Hx[j] - Hx[i]) + (HR[i, j] - HR[j, i])

whose two residual entropies require *both* rows' samples. Instead of
all-gathering the data, row blocks circulate around a ring: at step t each
device computes the I block between its own rows and the visiting block, adds
``min(0, I)^2`` into its own scores, and adds ``min(0, -I)^2`` into a score
accumulator that travels *with* the visiting block — the paper's messaging
mechanism (Section 3.1): one evaluation credits both endpoints.

Schedule: R devices in a flat ring. Blocks shift one hop per step; after
``R // 2`` processed steps every unordered block pair has met exactly once
(for even R the antipodal step t = R/2 sees both orders in flight, so the
lower-indexed device keeps it — the same dedup the paper's scheduler does
with its atomicCAS flags, done here statically). The accumulator then rides
the remaining hops home: total hops = R, so each block's credits arrive back
at its owner, which adds them to its locally accumulated scores.

Two-level form (``pod_axis``/``pod_size``): P pods of R shards each, the
hop plan from ``repro.utils.schedule.make_hier_plan``. Blocks circulate the
intra-pod ring every hop (neighbor-local wire) and cross the pod boundary
once per intra-pod revolution; because the intra rotation has period R, the
epoch-entry packet IS the packet the next epoch starts from, so the
cross-pod ppermute is issued at epoch *start* and a full revolution of
block compute hides its latency. The intra-pod block shifts stay
double-buffered (hop k+1's ppermute issued before hop k's compute); only
the credit/done riders — which depend on each hop's compute — move
sequentially, and they are 1/n the packet size. Both bodies count their
ppermute rounds at the call sites into a (4,) hop vector
(``schedule.HOP_*``: intra/cross x overlapped/sequential) that the order
driver threads out as device-measured wire counters; the counts equal the
plan's analytic ``hop_counts`` model by construction of the shared walk.
``pod_size=1`` is op-identical to the flat ring (same shifts, same
summation order — bit-identical scores).

Wire traffic per device is O(p/(P*R) * n) per step — the same as one block
of compute input — and the p x p statistic matrix is never materialized
globally. ``ring_find_root`` matches ``find_root_dense`` to f32 roundoff
(identical per-entry math; only the summation order differs).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.pairwise import (
    pair_moments,
    pair_stat_matrix,
    residual_entropy_block,
    row_entropies,
)
from repro.utils.schedule import (
    HOP_CROSS_OVL,
    HOP_CROSS_SEQ,
    HOP_INTRA_OVL,
    HOP_INTRA_SEQ,
    make_hier_plan,
    whole_chunk,
)


# ---------------------------------------------------------------------------
# schedule (pure, unit-testable)
# ---------------------------------------------------------------------------


def ring_steps(r: int) -> int:
    """Number of processed ring steps (excluding the intra-block step 0)."""
    return r // 2


def process_pair(r: int, t: int, dst, src):
    """Whether device ``dst`` processes the block from ``src`` at step ``t``.

    For even ``r`` the antipodal step ``t == r/2`` delivers each block pair
    to both endpoints simultaneously; the lower-indexed device keeps it.
    ``r`` and ``t`` are python ints; ``dst``/``src`` may be ints (schedule
    tests) or traced device indices (the executed ring body) — the result is
    a bool of the matching kind.
    """
    if t < 1 or t > ring_steps(r):
        return False
    if r % 2 == 0 and t == r // 2:
        return dst < src
    return True


# ---------------------------------------------------------------------------
# ring shift over one or two mesh axes
# ---------------------------------------------------------------------------


def _shift_by(x, s: int, axes: tuple, sizes: tuple):
    """Shift ``x`` by ``s`` hops along the flat (row-major over ``axes``)
    ring in one round of ppermutes: the device at flat index r receives the
    value from r - s (mod R)."""
    s %= math.prod(sizes)
    if s == 0:
        return x
    if len(axes) == 1:
        (a,), (n,) = axes, sizes
        return jax.lax.ppermute(x, a, [(k, (k + s) % n) for k in range(n)])
    # Two axes (outer, inner), row-major flat order r = o * n_i + i, with
    # s = a * n_i + b: the sender is (o - a, i - b), minus one more outer hop
    # for receivers whose inner index wraps (i < b).
    (ao, ai), (no, ni) = axes, sizes
    hop_o, hop_i = divmod(s, ni)
    y = x if hop_i == 0 else jax.lax.ppermute(
        x, ai, [(k, (k + hop_i) % ni) for k in range(ni)]
    )
    z1 = y if hop_o == 0 else jax.lax.ppermute(
        y, ao, [(k, (k + hop_o) % no) for k in range(no)]
    )
    if hop_i == 0:
        return z1
    z2 = jax.lax.ppermute(y, ao, [(k, (k + hop_o + 1) % no) for k in range(no)])
    i = jax.lax.axis_index(ai)
    return jax.tree.map(lambda u, v: jnp.where(i < hop_i, v, u), z1, z2)


def _flat_index(axes: tuple, sizes: tuple):
    """This device's flat ring index (row-major over ``axes``)."""
    r = jnp.zeros((), jnp.int32)
    for a, n in zip(axes, sizes):
        r = r * n + jax.lax.axis_index(a)
    return r


# ---------------------------------------------------------------------------
# the ring body
# ---------------------------------------------------------------------------


def _block_stat(x_own, x_vis, c_block, hx_own, hx_vis,
                sample_axis: str | None = None, backend: str = "xla"):
    """I block between own rows (rows of the result) and visiting rows.

    ``c_block[i, j] = c[own_i, vis_j]``. Both residual entropies of each pair
    are computed here — HR[i, j] and HR[j, i] — which is what lets one
    evaluation credit both endpoints (messaging). With ``sample_axis`` the
    rows carry only this device's n-shard and the entropy moments pmean over
    that axis. ``backend`` ``"pallas"``/``"pallas_fused"`` swaps the local
    moment reduction for the moments-emitting Pallas kernel — because the
    kernel exports raw (m1, m2) *sums*, the cross-shard pmean stays the same
    plain moment mean (``pairwise.finalize_moments``), so kernel-fed rings
    produce the same orders as the jnp-fed ones."""
    hr_fwd = residual_entropy_block(x_own, c_block, x_vis, sample_axis,
                                    backend=backend)
    hr_rev = residual_entropy_block(x_vis, c_block.T, x_own, sample_axis,
                                    backend=backend)
    return (hx_vis[None, :] - hx_own[:, None]) + (hr_fwd - hr_rev.T)


def _ring_body(x_loc, c_loc, mask_loc, *, ring_axes: tuple, ring_sizes: tuple,
               pod_axis: str | None = None, pod_size: int = 1,
               sample_axis: str | None = None, backend: str = "xla"):
    """Per-device ring schedule. x_loc: (m, n_loc); c_loc: (m, p); mask: (m,).

    Returns ``(score, hops)``: the (m,) score shard (inf on dead rows) and
    the static (4,) tuple of ppermute-round counts this trace issued (indexed
    by ``schedule.HOP_*``). ``sample_axis`` names the mesh axis the samples
    dimension is sharded over (None = replicated): every entropy moment
    reduction then runs on n/|sample_axis| local samples and is pmean'd — the
    packets that circulate shrink by the same factor, so both HBM *and* ring
    wire traffic drop with the sample shard count.

    ``pod_axis``/``pod_size`` select the two-level walk: blocks take one
    intra-pod hop per processed step (over ``ring_axes``) and one cross-pod
    hop per intra-pod revolution, per ``make_hier_plan(pod_size, R)``. The
    default ``pod_size=1`` plan IS the flat schedule — same shifts, same
    summation order as the pre-hierarchical body, bit-identical scores."""
    m = x_loc.shape[0]
    big_r = math.prod(ring_sizes)
    plan = make_hier_plan(pod_size, big_r)
    q_idx = jax.lax.axis_index(pod_axis) if pod_axis is not None else 0
    i_idx = _flat_index(ring_axes, ring_sizes)
    d_idx = q_idx * big_r + i_idx  # flat block index, pod-major

    hx_loc = row_entropies(x_loc, mask_loc, psum_axis=sample_axis)

    def credit(i_stat, pm, keep):
        fwd = jnp.where(pm, jnp.square(jnp.minimum(0.0, i_stat)), 0.0)
        rev = jnp.where(pm, jnp.square(jnp.minimum(0.0, -i_stat)), 0.0)
        k = keep.astype(fwd.dtype)
        return k * jnp.sum(fwd, axis=1), k * jnp.sum(rev, axis=0)

    # Offset (0, 0): intra-block pairs. One entropy pass gives the full HR
    # block; the antisymmetric stat is hr - hr.T (as in the dense path), so
    # the row-sum alone credits every ordered pair.
    c_intra = jax.lax.dynamic_slice_in_dim(c_loc, d_idx * m, m, axis=1)
    hr = residual_entropy_block(x_loc, c_intra, x_loc, sample_axis,
                                backend=backend)
    stat = pair_stat_matrix(hx_loc, hr)
    pm = mask_loc[:, None] & mask_loc[None, :] & ~jnp.eye(m, dtype=bool)
    score, _ = credit(stat, pm, jnp.asarray(True))

    tally = [0, 0, 0, 0]

    def shift(x, s, axes, sizes, kind):
        tally[kind] += 1
        return _shift_by(x, s, axes, sizes)

    # The plan walk. The visiting block (data + entropies + mask) is
    # immutable, so its movement is all *overlapped*: the intra-pod hop for
    # step t+1 is issued before step t's compute (double-buffering — the
    # ppermute has no data dependence on the running block compute), and the
    # cross-pod exchange for the next epoch is issued at this epoch's START
    # (the epoch-entry packet IS the next epoch's packet, because the intra
    # rotation has period R) so a full revolution of compute hides it. The
    # credit accumulator (the part compute mutates) travels as its own tiny
    # (m,) packet shifted after each step's credits are known; its wire cost
    # is 1/n of the block's, so serializing it hides nothing.
    acc = None
    prev = None
    cur = {"x": x_loc, "hx": hx_loc, "mask": mask_loc}
    for eidx, (e, ts) in enumerate(plan.epochs):
        nxt_entry = (
            shift(cur, 1, (pod_axis,), (pod_size,), HOP_CROSS_OVL)
            if eidx + 1 < len(plan.epochs) else None
        )
        pos = 0
        for j, (t, dedup) in enumerate(ts):
            if pos != t:  # advance the packet to this hop's offset
                cur = shift(cur, 1, ring_axes, ring_sizes, HOP_INTRA_OVL)
                pos = t
            nxt = (
                shift(cur, 1, ring_axes, ring_sizes, HOP_INTRA_OVL)
                if j + 1 < len(ts) else None
            )
            src = plan.src(e, t, q_idx, i_idx)
            keep = jnp.asarray(plan.keep(dedup, d_idx, src))
            c_vis = jax.lax.dynamic_slice_in_dim(c_loc, src * m, m, axis=1)
            stat = _block_stat(x_loc, cur["x"], c_vis, hx_loc, cur["hx"],
                               sample_axis, backend=backend)
            pm = mask_loc[:, None] & cur["mask"][None, :]
            fwd, rev = credit(stat, pm, keep)
            score = score + fwd
            # acc rides with the block: shift the previous hops' credits to
            # the block's new position, add this hop's. After hop (e, t) it
            # holds all credits for block (q - e, i - t).
            if acc is None:
                acc = rev
            else:
                dt = (t - prev[1]) % big_r
                de = (e - prev[0]) % pod_size
                if dt:
                    acc = shift(acc, dt, ring_axes, ring_sizes, HOP_INTRA_SEQ)
                if de:
                    acc = shift(acc, de, (pod_axis,), (pod_size,),
                                HOP_CROSS_SEQ)
                acc = acc + rev
            prev = (e, t)
            if nxt is not None:
                cur, pos = nxt, t + 1
        cur = nxt_entry

    # Ride the accumulator the rest of the way home (one multi-hop round per
    # level: each block's credits land back at its owner).
    if acc is not None:
        dt = (-prev[1]) % big_r
        de = (-prev[0]) % pod_size
        if dt:
            acc = shift(acc, dt, ring_axes, ring_sizes, HOP_INTRA_SEQ)
        if de:
            acc = shift(acc, de, (pod_axis,), (pod_size,), HOP_CROSS_SEQ)
        score = score + acc
    return jnp.where(mask_loc, score, jnp.inf), tuple(tally)


def _ring_threshold_body(x_loc, c_loc, mask_loc, *, ring_axes: tuple,
                         ring_sizes: tuple, pod_axis: str | None = None,
                         pod_size: int = 1, sample_axis: str | None = None,
                         gamma0: float = 1e-5, gamma_growth: float = 2.0,
                         chunk: int = 16, max_rounds: int = 100_000):
    """The paper's threshold state machine (Algorithms 4-6) run per ring
    shard — the comparison-saving evaluation composed with the messaging
    ring, replacing one dense ``_ring_body`` sweep.

    Per-device state mirrors the host machine restricted to the resident
    rows: an ``(m_l,)`` score shard, an ``(m_l, m)`` done matrix over all
    global columns, and the globally consistent gamma/round/terminal
    scalars. One ``lax.while_loop`` *cycle* is a full ring pass:

      * hop 0 processes intra-block pending pairs (mutual simultaneous
        comparisons dedup'd by the lower-index rule, exactly as the host
        machine's Alg. 6 line 22);
      * hops 1..R//2 process the visiting block's columns — each *active*
        own row (below gamma, unfinished, live) evaluates its first pending
        chunk of the visitor, with the antipodal ``process_pair`` dedup
        assigning every unordered block pair to exactly one hosting
        endpoint per cycle. The *visiting* rows initiate too, from their
        cycle-start activity riding the packet: a below-gamma visitor's
        pending pairs against the host's rows are processed at the same
        hop (dedup'd against the host-initiated picks), so every active
        worker makes chunk progress each cycle no matter which side of
        the block-pair assignment it sits on — without this, a pair whose
        statically assigned host row is paused would stall until gamma
        inflated past the partner, burning the comparison savings;
      * messaging credits to the visiting rows and their symmetric done
        marks ride the packet as riders (an ``(m_l,)`` credit vector and an
        ``(m_l, m)`` done update), shifted home with the block after the
        last processed hop — total hops == R, so every rider lands back at
        its owner before the cycle's bookkeeping.

    The cycle epilogue is where the distributed machine re-joins the
    paper's scheduler: the cycle's kept-comparison count is psum'd (zero
    processed -> grow gamma by ``gamma_growth``, Alg. 6 lines 15-17 — this
    also covers the ring-only stall where every pending pair's initiating
    endpoint is paused), and Algorithm 6's termination condition is
    evaluated on psum'd below-gamma finished/unfinished counts so every
    shard agrees on the same terminal cycle. Correctness then follows the
    paper's Section 3.2 argument unchanged: at termination every
    below-gamma worker is finished with a *complete* score, every paused
    worker's partial score only grows, so ``argmin`` over the gathered
    scores is the true root no matter how the chunks were scheduled across
    shards.

    In the two-level form (``pod_axis``/``pod_size``) one cycle walks
    ``make_hier_plan(pod_size, R)`` instead of the flat hop sequence: the
    immutable block packet (data, entropies, mask, departure-time score and
    finished snapshot) moves on the overlapped schedule — next intra hop
    prefetched before this hop's compute, the cross-pod exchange issued a
    full intra revolution ahead — while the credit/done riders, which DO
    depend on each hop's compute, catch up sequentially right before the hop
    that consumes them. Rider values are bit-identical to shifting the whole
    packet at once (the immutable parts carry no state, and a rider at hop k
    is exactly the rider updated at hop k-1 moved by the same block delta),
    so threshold credits/done-marks/finished-bits ride unchanged.

    Returns ``(scores, comparisons, rounds, converged, hops)``: the
    ``(m_l,)`` score shard (inf on dead rows; partial above gamma — fine for
    the argmin) plus replicated device-measured counters; ``hops`` is the
    (4,) int32 ppermute-round tally (``schedule.HOP_*``) = rounds x the
    per-cycle walk. ``converged`` is False iff ``max_rounds`` cut the loop
    before termination held.
    """
    m_l = x_loc.shape[0]
    big_r = math.prod(ring_sizes)
    plan = make_hier_plan(pod_size, big_r)
    m = m_l * pod_size * big_r
    all_axes = ((pod_axis,) + tuple(ring_axes) if pod_axis is not None
                else tuple(ring_axes))
    q_idx = jax.lax.axis_index(pod_axis) if pod_axis is not None else 0
    i_idx = _flat_index(ring_axes, ring_sizes)
    r_idx = q_idx * big_r + i_idx  # flat block index, pod-major

    hx_loc = row_entropies(x_loc, mask_loc, psum_axis=sample_axis)
    mask_all = jax.lax.all_gather(mask_loc, all_axes, tiled=True)  # (m,)
    own_gid = r_idx * m_l + jnp.arange(m_l, dtype=jnp.int32)  # global row ids
    pv = (mask_loc[:, None] & mask_all[None, :]
          & (own_gid[:, None] != jnp.arange(m, dtype=jnp.int32)[None, :]))
    has_pairs = jnp.sum(mask_all) >= 2

    # Chunk rounded to a divisor of the block width so the visiting columns
    # reshape into whole chunks; the host machine applies the same rounding
    # to its row count.
    b = whole_chunk(chunk, m_l)
    nc = m_l // b
    rows = jnp.broadcast_to(jnp.arange(m_l)[:, None], (m_l, b))

    def hop(s, d, gamma, comps, credit, done, x_vis, hx_vis, mask_vis,
            s_vis, fin_vis, src, keep_flag, intra: bool):
        """Process one visiting block (``intra``: own block). ``keep_flag``
        is the plan's dedup predicate for this hop (True off the
        self-conjugate offsets). Returns the updated own state and the
        visitor's riders."""
        col0 = src * m_l
        vis_gid = col0 + jnp.arange(m_l, dtype=jnp.int32)
        d_vis = jax.lax.dynamic_slice(d, (0, col0), (m_l, m_l))
        pv_vis = (mask_loc[:, None] & mask_vis[None, :]
                  & (own_gid[:, None] != vis_gid[None, :]))
        pending = ~d_vis & pv_vis  # (m_l, m_l)

        fin = jnp.all(d, axis=1)
        active = (s < gamma) & ~fin & mask_loc

        # --- host-initiated: each active own row's first pending chunk of
        # the visiting columns.
        pend_chunk = jnp.any(pending.reshape(m_l, nc, b), axis=2)
        ci = jnp.argmax(pend_chunk, axis=1)  # first pending chunk per row
        cols = ci[:, None] * b + jnp.arange(b)[None, :]  # (m_l, b) vis-local
        cols_g = col0 + cols
        xj = x_vis[cols.reshape(-1)].reshape(m_l, b, -1)
        c_vals = jnp.take_along_axis(c_loc, cols_g, axis=1)
        hr_fwd, hr_rev = pair_moments(x_loc, c_vals, xj,
                                      psum_axis=sample_axis)
        stat = (hx_vis[cols] - hx_loc[:, None]) + (hr_fwd - hr_rev)

        proc = active[:, None] & jnp.take_along_axis(pending, cols, axis=1)
        if intra:
            # Intra-block: both endpoints resident, so simultaneous mutual
            # proposals are possible — lower index keeps (host dedup rule).
            prop = jnp.zeros((m_l, m_l), bool).at[rows, cols].max(proc)
            partner_also = jnp.take_along_axis(prop.T, cols, axis=1)
            keep = proc & (~partner_also | (rows < cols))
        else:
            # Cross-block: the plan assigns each unordered block pair to
            # exactly one hosting endpoint per cycle (at self-conjugate
            # offsets the lower flat-indexed device keeps both directions).
            keep = proc & keep_flag

        fwd = jnp.where(keep, jnp.square(jnp.minimum(0.0, stat)), 0.0)
        rev = jnp.where(keep, jnp.square(jnp.minimum(0.0, -stat)), 0.0)
        s2 = s + jnp.sum(fwd, axis=1)
        d2 = d.at[rows, cols_g].max(keep)
        comps2 = comps + jnp.sum(keep).astype(comps.dtype)
        if intra:
            # Both endpoints are own rows: credit + symmetric done locally.
            # Intra-block is already bidirectional (every active own row
            # initiates), so there is no visitor-initiated pass.
            s2 = s2.at[cols.reshape(-1)].add(rev.reshape(-1))
            d2 = d2.at[cols, own_gid[rows]].max(keep)
            return s2, d2, comps2, credit, done
        credit2 = credit.at[cols.reshape(-1)].add(rev.reshape(-1))
        done2 = done.at[cols, own_gid[rows]].max(keep)

        # --- visitor-initiated: each *active* visiting row processes its
        # first pending chunk of the HOST's columns, dedup'd against this
        # hop's host-initiated picks. Without this pass a pair's progress
        # would be chained to its statically assigned host row's activity,
        # stalling below-gamma visitors. The visitor's partial score is its
        # departure-time score riding the packet PLUS the credits earned so
        # far this cycle (the credit rider) — an underestimate only by the
        # visitor's home-side accrual, so a visitor crossing gamma in
        # flight pauses at the very next host, like the host machine's
        # per-round re-check.
        pm_hop = jnp.zeros((m_l, m_l), bool).at[rows, cols].max(keep)
        pending2 = pending.T & ~pm_hop.T  # (vis rows, own cols)
        pend_chunk2 = jnp.any(pending2.reshape(m_l, nc, b), axis=2)
        ci2 = jnp.argmax(pend_chunk2, axis=1)
        cols2 = ci2[:, None] * b + jnp.arange(b)[None, :]  # (m_l, b) own-local
        xj2 = x_loc[cols2.reshape(-1)].reshape(m_l, b, -1)
        c_vals2 = c_loc[cols2, vis_gid[:, None]]  # c[own i, vis j] == c[j, i]
        hr_fwd2, hr_rev2 = pair_moments(x_vis, c_vals2, xj2,
                                        psum_axis=sample_axis)
        stat2 = (hx_loc[cols2] - hx_vis[:, None]) + (hr_fwd2 - hr_rev2)

        act_vis = (s_vis + credit < gamma) & ~fin_vis & mask_vis
        keep2 = (act_vis[:, None]
                 & jnp.take_along_axis(pending2, cols2, axis=1)
                 & keep_flag)
        fwd2 = jnp.where(keep2, jnp.square(jnp.minimum(0.0, stat2)), 0.0)
        rev2 = jnp.where(keep2, jnp.square(jnp.minimum(0.0, -stat2)), 0.0)
        s2 = s2.at[cols2.reshape(-1)].add(rev2.reshape(-1))
        d2 = d2.at[cols2, vis_gid[rows]].max(keep2)
        credit2 = credit2 + jnp.sum(fwd2, axis=1)
        done2 = done2.at[rows, own_gid[cols2]].max(keep2)
        comps2 = comps2 + jnp.sum(keep2).astype(comps.dtype)
        return s2, d2, comps2, credit2, done2

    cdtype = jnp.int32
    state0 = dict(
        s=jnp.where(mask_loc, 0.0, jnp.inf).astype(x_loc.dtype),
        d=~pv,
        gamma=jnp.asarray(gamma0, x_loc.dtype),
        comparisons=jnp.asarray(0, cdtype),
        rounds=jnp.asarray(0, jnp.int32),
        terminal=jnp.asarray(False),
    )

    cycle_tally = {"v": (0, 0, 0, 0)}

    def cycle(st):
        s, d, gamma = st["s"], st["d"], st["gamma"]
        comps = jnp.asarray(0, cdtype)
        zero_credit = jnp.zeros((m_l,), x_loc.dtype)
        zero_done = jnp.zeros((m_l, m), bool)

        tally = [0, 0, 0, 0]

        def shift(x, sft, axes, sizes, kind):
            tally[kind] += 1
            return _shift_by(x, sft, axes, sizes)

        # Offset (0, 0): intra-block pairs (no packet, no riders; the
        # visitor arguments are unused on the intra hop).
        s, d, comps, _, _ = hop(s, d, gamma, comps, zero_credit, zero_done,
                                x_loc, hx_loc, mask_loc, s, jnp.all(d, axis=1),
                                r_idx, jnp.asarray(True), True)

        # The plan walk. The *immutable* part of the packet — block data,
        # entropies, mask, plus the departure-time score and finished
        # snapshot remote hosts gate visitor-initiated work on — moves on
        # the overlapped schedule (next intra hop prefetched before this
        # hop's compute; the cross-pod exchange issued an epoch early). The
        # credit/done riders depend on each hop's compute, so they catch up
        # sequentially: shifted by the same block delta right before the
        # hop that consumes them — values bit-identical to moving the whole
        # packet at once, at 1/n the overlapped wire cost.
        cur = {"x": x_loc, "hx": hx_loc, "mask": mask_loc,
               "s0": s, "fin": jnp.all(d, axis=1)}
        credit_r, done_r = zero_credit, zero_done
        prev = None
        for eidx, (e, ts) in enumerate(plan.epochs):
            nxt_entry = (
                shift(cur, 1, (pod_axis,), (pod_size,), HOP_CROSS_OVL)
                if eidx + 1 < len(plan.epochs) else None
            )
            pos = 0
            for j, (t, dedup) in enumerate(ts):
                if pos != t:  # advance the packet to this hop's offset
                    cur = shift(cur, 1, ring_axes, ring_sizes, HOP_INTRA_OVL)
                    pos = t
                nxt = (
                    shift(cur, 1, ring_axes, ring_sizes, HOP_INTRA_OVL)
                    if j + 1 < len(ts) else None
                )
                if prev is not None:  # riders catch up to this hop
                    riders = {"credit": credit_r, "done": done_r}
                    dt = (t - prev[1]) % big_r
                    de = (e - prev[0]) % pod_size
                    if dt:
                        riders = shift(riders, dt, ring_axes, ring_sizes,
                                       HOP_INTRA_SEQ)
                    if de:
                        riders = shift(riders, de, (pod_axis,), (pod_size,),
                                       HOP_CROSS_SEQ)
                    credit_r, done_r = riders["credit"], riders["done"]
                src = plan.src(e, t, q_idx, i_idx)
                keep_flag = jnp.asarray(plan.keep(dedup, r_idx, src))
                s, d, comps, credit_r, done_r = hop(
                    s, d, gamma, comps, credit_r, done_r,
                    cur["x"], cur["hx"], cur["mask"], cur["s0"], cur["fin"],
                    src, keep_flag, False,
                )
                prev = (e, t)
                if nxt is not None:
                    cur, pos = nxt, t + 1
            cur = nxt_entry
        if prev is not None:
            # Ride the riders the rest of the way home (one multi-hop round
            # per level: every rider lands back at its owner).
            riders = {"credit": credit_r, "done": done_r}
            dt = (-prev[1]) % big_r
            de = (-prev[0]) % pod_size
            if dt:
                riders = shift(riders, dt, ring_axes, ring_sizes,
                               HOP_INTRA_SEQ)
            if de:
                riders = shift(riders, de, (pod_axis,), (pod_size,),
                               HOP_CROSS_SEQ)
            s = s + riders["credit"]
            d = d | riders["done"]
        cycle_tally["v"] = tuple(tally)

        # Cycle epilogue: globally consistent gamma/termination bookkeeping.
        processed = jax.lax.psum(comps, all_axes)
        gamma2 = jnp.where(processed > 0, gamma,
                           gamma * jnp.asarray(gamma_growth, gamma.dtype))
        fin = jnp.all(d, axis=1)
        below = (s < gamma2) & mask_loc
        n_bf = jax.lax.psum(jnp.sum(below & fin), all_axes)
        n_bu = jax.lax.psum(jnp.sum(below & ~fin), all_axes)
        return dict(
            s=s, d=d, gamma=gamma2,
            comparisons=st["comparisons"] + processed,
            rounds=st["rounds"] + 1,
            terminal=(n_bf > 0) & (n_bu == 0),
        )

    def cond(st):
        return ~st["terminal"] & (st["rounds"] < max_rounds) & has_pairs

    final = jax.lax.while_loop(cond, cycle, state0)
    scores = jnp.where(mask_loc, final["s"], jnp.inf)
    # Device-measured wire counters: the per-cycle walk is static (tallied
    # while tracing ``cycle``), the cycle count is not — total rounds x the
    # per-cycle (4,) tally, zero when the loop never ran.
    hops = (final["rounds"].astype(jnp.int32)
            * jnp.asarray(cycle_tally["v"], jnp.int32))
    return (scores, final["comparisons"], final["rounds"],
            final["terminal"] | ~has_pairs, hops)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def ring_find_root(xn, c, mask, mesh, row_axes: tuple | None = None,
                   unroll: bool = False, sample_axis: str | None = None,
                   score_backend: str = "auto"):
    """Distributed find-root. Returns ``(root_idx, scores)`` == dense.

    ``row_axes`` names the mesh axes the p rows shard over (ring axes);
    defaults to the DP axes present in ``mesh``. ``sample_axis`` optionally
    names a further mesh axis (typically ``"model"``) to shard the samples
    axis n over: entropy moments are then computed on n/|sample_axis| local
    samples and pmean'd (pairwise.stream_entropy), cutting the dominant
    (m, n) buffer and the circulating packets by the same factor. Axes in
    neither set run the ring replicated. Falls back to the dense single-shard
    evaluation when the ring is degenerate (one shard, or p not divisible by
    the shard count); ``sample_axis`` is dropped when n doesn't divide.
    ``unroll`` is accepted for signature parity with the dense path: the ring
    schedule is always a statically unrolled python loop (R is a mesh
    constant). ``score_backend`` selects the per-shard moment reduction
    (``kernels.ops.SCORE_BACKENDS``); both ``pallas*`` names map to the
    moments-emitting square kernel — the fused triangular kernel finalizes
    its scores in-kernel and therefore has nothing to psum, so the ring's
    kernel route is always the raw-sum emitter + ``finalize_moments``.

    A *leading* ``"pod"`` axis of size > 1 in ``row_axes`` selects the
    two-level ring (pods are NOT flattened away): blocks circulate the
    remaining axes as the intra-pod ring every hop and cross the pod
    boundary once per intra-pod revolution, per
    ``utils.schedule.make_hier_plan``. Block ownership is pod-major
    (flat index q * R + i), so the sharding layout — and the recovered
    scores' row order — match the flat ring over the same axes.
    """
    del unroll
    from repro.kernels import ops as kops

    backend = kops.select_backend(score_backend)
    sizes = dict(mesh.shape)
    if row_axes is None:
        row_axes = tuple(a for a in ("pod", "data") if a in sizes)
    row_axes = tuple(a for a in row_axes if sizes.get(a, 1) > 1)
    pod_axis = None
    pod_size = 1
    ring_axes = row_axes
    if len(row_axes) >= 2 and row_axes[0] == "pod":
        pod_axis, ring_axes = row_axes[0], row_axes[1:]
        pod_size = sizes[pod_axis]
    big_r = 1
    for a in row_axes:
        big_r *= sizes[a]
    p, n = xn.shape

    if big_r <= 1 or p % big_r != 0 or len(ring_axes) > 2:
        from repro.core.pairwise import dense_scores

        s, _, _ = dense_scores(xn, c, mask, block_j=min(32, p))
        return jnp.argmin(s), s

    if sample_axis is not None and (
        sample_axis in row_axes
        or sizes.get(sample_axis, 1) <= 1
        or n % sizes[sample_axis] != 0
    ):
        sample_axis = None
    x_spec = P(row_axes, sample_axis)

    ring_sizes = tuple(sizes[a] for a in ring_axes)
    body = jax.shard_map(
        lambda x, cm, mk: _ring_body(
            x, cm, mk, ring_axes=ring_axes, ring_sizes=ring_sizes,
            pod_axis=pod_axis, pod_size=pod_size,
            sample_axis=sample_axis, backend=backend,
        )[0],
        mesh=mesh,
        in_specs=(x_spec, P(row_axes, None), P(row_axes)),
        out_specs=P(row_axes),
        check_vma=False,
    )
    scores = body(xn, c, mask)
    return jnp.argmin(scores), scores


def ring_find_root_jit(mesh, score_backend: str = "auto",
                       topology: tuple | None = None):
    """jit-compiled ring find-root over *all* devices of ``mesh``.

    By default a mesh WITHOUT a ``"pod"`` axis (or with a size-1 one) is
    flattened to a single ``ring`` axis so every device owns one row block —
    the paper's worker decomposition with workers == devices. A mesh whose
    ``"pod"`` axis has size > 1 keeps it: the remaining devices flatten into
    the intra-pod ``ring`` axis and the find-root runs the two-level plan.
    ``topology=(P, R)`` overrides both (must factor the device count);
    ``(1, R)`` forces the flat ring — the degenerate-axis escape hatch the
    pod=1 bit-identity test pins.
    """
    n_dev = mesh.devices.size
    if topology is None:
        pods = dict(mesh.shape).get("pod", 1)
        topology = (pods, n_dev // pods)
    pods, ring = topology
    if pods * ring != n_dev:
        raise ValueError(
            f"topology {topology} does not factor {n_dev} devices")
    if pods > 1:
        hier = Mesh(mesh.devices.reshape(pods, ring), ("pod", "ring"))
        row_axes = ("pod", "ring")
    else:
        hier = Mesh(mesh.devices.reshape(-1), ("ring",))
        row_axes = ("ring",)

    @jax.jit
    def fn(xn, c, mask):
        return ring_find_root(xn, c, mask, hier, row_axes=row_axes,
                              score_backend=score_backend)

    def call(xn, c, mask):
        # shard_map wants the context mesh to be the one it maps over, so
        # ``hier`` stands in for any ``jax.set_mesh`` mesh of the caller.
        with jax.set_mesh(hier):
            return fn(xn, c, mask)

    return call
