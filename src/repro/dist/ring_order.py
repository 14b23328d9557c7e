"""Ring-parallel *full* causal order: the paper's Section 3.1 worker ring
promoted from a find-root helper (dist/ring.py) to the driver of all p
DirectLiNGAM iterations.

``causal_order_ring`` keeps the per-device row blocks, correlation rows and
credit accumulators device-resident across the whole recovery on a 3-axis
``("pod", "ring", "model")`` mesh:

  * **pod x ring axes** — the p rows (and the matching correlation rows)
    shard into contiguous blocks over the P x R row grid (flat block index
    q * R + i, pod-major), exactly as in ``ring_find_root``. Each outer
    iteration runs the two-level messaging schedule from
    ``utils.schedule.make_hier_plan`` (blocks circulate the intra-pod ring
    every hop, cross the pod boundary once per intra-pod revolution, one
    evaluation credits both endpoints, antipodal dedup across both levels;
    P=1 IS the flat ring), picks the global root from the all-gathered
    (m,)-score vector, then applies the Eq. (10)/(11) rank-1 data +
    covariance updates *in place on each shard* — only the root's data row
    (n/|model| floats) and correlation row (m floats) cross the wire, never
    the blocks themselves. The ordered row is re-masked, not re-sharded.
  * **model axis** — the samples axis n shards over ``model`` inside the ring
    body: every entropy moment reduction (``pairwise.stream_entropy``) runs
    on n/|model| local samples and the two Hyvarinen moments are pmean'd
    before the nonlinear entropy epilogue. This cuts the dominant (m, n) data
    buffer per device — and the circulating block packets — by the model
    shard count.

Each iteration evaluates either the dense messaging ring (``_ring_body``)
or — with ``threshold=True`` — the paper's comparison-saving threshold state
machine run *per shard* (``_ring_threshold_body``: pending chunks processed
per hop for resident AND visiting rows, credits/done-masks riding the
packet, gamma growth and termination psum'd ring-wide; see dist/ring.py).

The outer loop consumes the topology-aware bucket plan shared with the scan
driver (``repro.utils.schedule.make_schedule`` with
``ring=R``): block sizes stay static within a stage, so the ring schedule
compiles once per stage (<= log2 p specializations), and the <= log2 p
stage transitions compact live rows with a device-side
``jnp.nonzero(size=m)`` gather — the only points where rows move between
shards. Everything runs in ONE jit dispatch, like ``causal_order_scan``.

Exactness: identical causal orders to ``causal_order`` (host driver),
``causal_order_scan`` and the serial numpy oracle, dense AND thresholded;
scores match the dense evaluation to f32 summation order (asserted across
1/2/4/8-shard rings in tests/test_ring_order.py and
tests/test_ring_threshold.py, which the CI ``multidevice`` lane runs on 8
forced host devices).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.covariance import VAR_EPS, cov_matrix, normalize, rank1_gates
from repro.dist.ring import _ring_body, _ring_threshold_body
from repro.utils import tracing
from repro.utils.schedule import make_schedule
from repro.utils.shapes import next_pow2


# ---------------------------------------------------------------------------
# schedule (pure, unit-testable)
# ---------------------------------------------------------------------------


def ring_order_stages(p: int, min_bucket: int, r: int,
                      chunk: int = 1) -> list[tuple[int, int]]:
    """Static stage plan ``[(buffer size m, iteration count), ...]``.

    The topology-aware :func:`repro.utils.schedule.make_schedule` with ring
    size ``r``: each stage's m is a multiple of ``r`` (so the m/r-row blocks
    stay non-empty and equal), a power of two after the top stage (which is
    p rounded up to the bucket floor and, for the threshold machine's
    ``chunk``, to whole chunks per shard), and >= the live-row count of
    every iteration it covers. Total iterations sum to p - 1 (the last live
    row needs no find-root). With r=1 this IS the scan schedule
    (``core.paralingam._scan_stages``) — the two drivers consume the same
    ``Schedule`` object and cannot drift."""
    return list(make_schedule(p, min_bucket, ring=r, chunk=chunk).stages)


# ---------------------------------------------------------------------------
# the staged ring driver (one jit dispatch)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _make_ring_order_fn(mesh: Mesh, sample_axis: str | None, p: int, n: int,
                        min_bucket: int, backend: str = "xla",
                        threshold: bool = False, chunk: int = 16,
                        gamma0: float = 1e-5, gamma_growth: float = 2.0,
                        max_rounds: int = 100_000):
    """Build the jitted staged ring driver for one (mesh, problem) shape.

    Cached on the canonical mesh + static shape (+ concrete score backend
    + the threshold machine's static knobs) so repeated fits reuse the
    compiled executable (jax Mesh hashes by device ids + axis names).
    ``backend`` ``"pallas"``/``"pallas_fused"`` feeds the ring bodies'
    entropy reductions from the moments-emitting kernel; the psum seam is
    unchanged because the kernel exports raw (m1, m2) sums (see
    ``dist/ring._block_stat``). ``threshold=True`` swaps each iteration's
    dense ring sweep for the per-shard threshold state machine
    (``dist.ring._ring_threshold_body``) — same argmin-root contract, with
    device-measured comparison/round/convergence counters instead of the
    dense path's analytic ones."""
    pods = int(dict(mesh.shape).get("pod", 1))
    big_r = mesh.shape["ring"]
    shards = pods * big_r
    row_axes = ("pod", "ring")
    sched = make_schedule(p, min_bucket, ring=big_r, pods=pods,
                          sample_shards=int(dict(mesh.shape).get("model", 1)),
                          chunk=chunk if threshold else 1)
    stages = list(sched.stages)
    cdtype = jnp.int32

    def make_stage(m: int, cnt: int, pos: int):
        m_l = m // shards

        def iteration(k, st, ig_all):
            (x_loc, c_loc, mk, ig, order, comps_it, rounds_it, conv_it,
             hops_it) = st
            mk_all = jax.lax.all_gather(mk, row_axes, tiled=True)
            # --- find root: messaging ring over the live blocks ---
            with jax.named_scope("find_root"):
                if threshold:
                    scores, comps, rounds, conv, hops = _ring_threshold_body(
                        x_loc, c_loc, mk, ring_axes=("ring",),
                        ring_sizes=(big_r,), pod_axis="pod", pod_size=pods,
                        sample_axis=sample_axis,
                        gamma0=gamma0, gamma_growth=gamma_growth,
                        chunk=chunk, max_rounds=max_rounds,
                    )
                else:
                    scores, hop_tally = _ring_body(
                        x_loc, c_loc, mk, ring_axes=("ring",),
                        ring_sizes=(big_r,), pod_axis="pod", pod_size=pods,
                        sample_axis=sample_axis, backend=backend,
                    )
                    hops = jnp.asarray(hop_tally, jnp.int32)
                    r = jnp.sum(mk_all).astype(cdtype)  # live rows this iteration
                    comps = r * (r - 1) // 2
                    rounds = jnp.asarray(0, jnp.int32)
                    conv = jnp.asarray(True)
                s_all = jax.lax.all_gather(scores, row_axes, tiled=True)  # (m,)
                root = jnp.argmin(s_all).astype(jnp.int32)  # stage-buffer index
            order = order.at[pos + k].set(ig_all[root])
            comps_it = comps_it.at[pos + k].set(comps)
            rounds_it = rounds_it.at[pos + k].set(rounds.astype(jnp.int32))
            conv_it = conv_it.at[pos + k].set(conv)
            hops_it = hops_it.at[pos + k].set(hops)

            with jax.named_scope("update"):
                # --- broadcast the root's rows: the only per-iteration wire
                # traffic besides the (m,) score/mask gathers. x_root is the
                # *local sample shard* of the root row ((n/|model|,)), c_root its
                # full correlation row ((m,)).
                my = (jax.lax.axis_index("pod") * big_r
                      + jax.lax.axis_index("ring"))
                owns = (my == root // m_l)
                r_l = root % m_l
                x_root = jax.lax.psum(
                    jnp.where(
                        owns, jax.lax.dynamic_index_in_dim(x_loc, r_l, 0, False),
                        0.0,
                    ),
                    row_axes,
                )
                c_root = jax.lax.psum(
                    jnp.where(
                        owns, jax.lax.dynamic_index_in_dim(c_loc, r_l, 0, False),
                        0.0,
                    ),
                    row_axes,
                )

                # --- UpdateData (Alg. 7, Eq. 10) on own rows, in place.
                # Matches covariance.update_data: dead + root rows pass through
                # (b = 0, s = 1, scale = 1).
                row_ids = my * m_l + jnp.arange(m_l, dtype=jnp.int32)
                live = mk & (row_ids != root)
                b_raw = jax.lax.dynamic_index_in_dim(c_loc, root, 1, False)  # (m_l,)
                b, s_row = rank1_gates(b_raw, live)
                out = (x_loc - b[:, None] * x_root[None, :]) / s_row[:, None]
                sq = jnp.sum(jnp.square(out), axis=1)
                if sample_axis is not None:
                    sq = jax.lax.psum(sq, sample_axis)
                var = sq / max(n - 1, 1)
                scale = jnp.where(live, jax.lax.rsqrt(jnp.maximum(var, VAR_EPS)), 1.0)
                x2 = out * scale[:, None]

                # --- UpdateCovMat (Alg. 8, Eq. 11) on own rows x all columns.
                # b over columns comes from the broadcast root row (c is exactly
                # symmetric), gated by the *global* live mask so dead columns
                # pass through — same contract as covariance.update_cov.
                col_ids = jnp.arange(m, dtype=jnp.int32)
                col_live = mk_all & (col_ids != root)
                b_col, s_col = rank1_gates(c_root, col_live)
                c2 = jnp.clip(
                    (c_loc - b[:, None] * b_col[None, :])
                    / (s_row[:, None] * s_col[None, :]),
                    -1.0, 1.0,
                )
                c2 = jnp.where(row_ids[:, None] == col_ids[None, :], 1.0, c2)

            # --- retire the root: re-mask, don't re-shard.
            mk2 = mk & (row_ids != root)
            return (x2, c2, mk2, ig, order, comps_it, rounds_it, conv_it,
                    hops_it)

        def body(x_loc, c_loc, mk_loc, ig_loc, order, comps_it, rounds_it,
                 conv_it, hops_it):
            # The row-id -> variable-id map only changes at compactions, so
            # its gather runs once per stage, not once per iteration.
            ig_all = jax.lax.all_gather(ig_loc, row_axes, tiled=True)
            return jax.lax.fori_loop(
                0, cnt, lambda k, st: iteration(k, st, ig_all),
                (x_loc, c_loc, mk_loc, ig_loc, order, comps_it, rounds_it,
                 conv_it, hops_it),
            )

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(row_axes, sample_axis), P(row_axes, None), P(row_axes),
                P(row_axes), P(), P(), P(), P(), P(),
            ),
            out_specs=(
                P(row_axes, sample_axis), P(row_axes, None), P(row_axes),
                P(row_axes), P(), P(), P(), P(), P(),
            ),
            check_vma=False,
        )

    stage_fns = []
    pos = 0
    for m, cnt in stages:
        stage_fns.append((m, cnt, make_stage(m, cnt, pos)))
        pos += cnt

    @jax.jit
    def run(xn, c):
        order = jnp.zeros((p,), jnp.int32)
        comps_it = jnp.zeros((p,), cdtype)
        rounds_it = jnp.zeros((p,), jnp.int32)
        conv_it = jnp.ones((p,), bool)
        hops_it = jnp.zeros((p, 4), jnp.int32)
        idx_g = jnp.arange(p, dtype=jnp.int32)
        xb, cb = xn, c
        mloc = jnp.ones((p,), bool)
        m_cur = p
        pos = 0
        for m, cnt, stage in stage_fns:
            if m != m_cur:
                # Compaction (or the initial pad to the top stage): the only
                # point rows move between shards — <= log2 p times per
                # recovery, vs the host driver's re-gather every iteration.
                with jax.named_scope("compact"):
                    live = p - pos  # static: one root retires per iteration
                    sel = jnp.nonzero(mloc, size=m, fill_value=0)[0].astype(jnp.int32)
                    idx_g = idx_g[sel]
                    xb = xb[sel]
                    cb = cb[sel][:, sel]
                    mloc = jnp.arange(m) < live
                m_cur = m
            with jax.named_scope(f"order_{m}"):
                (xb, cb, mloc, idx_g, order, comps_it, rounds_it, conv_it,
                 hops_it) = stage(
                    xb, cb, mloc, idx_g, order, comps_it, rounds_it, conv_it,
                    hops_it
                )
            pos += cnt
        # One live row remains; no find-root needed (matches the host driver).
        order = order.at[p - 1].set(idx_g[jnp.argmax(mloc)])
        return order, comps_it, rounds_it, conv_it, hops_it

    return run


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def _canonical_mesh(mesh, n: int, pods: int | None = None):
    """Canonicalize any mesh to the 3-axis ``("pod", "ring", "model")`` form.

    The model size is taken from the given mesh's ``model`` axis (1 when
    absent); the remaining devices split into ``pods`` rings (``pods``
    defaults to the mesh's own ``pod`` axis size, 1 when absent — a flat
    ring with a degenerate pod axis). Returns
    ``(canon_mesh, pods, ring_size, sample_axis)`` with ``sample_axis`` None
    when the samples axis cannot shard (no model axis, or n not divisible).
    Raises ``ValueError`` when ``pods`` does not divide the row-shard
    count — the caller turns an explicit-topology mismatch into a
    ``ConfigError``."""
    if mesh is None:
        mesh = jax.sharding.get_mesh()  # the ``jax.set_mesh`` mesh, if any
    if mesh.empty:
        devs = np.array(jax.devices())
        msize = 1
        mesh_pods = 1
    else:
        devs = np.asarray(mesh.devices).reshape(-1)
        msize = int(dict(mesh.shape).get("model", 1))
        mesh_pods = int(dict(mesh.shape).get("pod", 1))
    total = devs.size
    rows = total // msize
    if pods is None:
        pods = mesh_pods if rows % mesh_pods == 0 else 1
    if pods < 1 or rows % pods:
        raise ValueError(
            f"pod count {pods} does not divide the {rows} row shards")
    big_r = rows // pods
    canon = Mesh(devs.reshape(pods, big_r, msize), ("pod", "ring", "model"))
    sample_axis = "model" if (msize > 1 and n % msize == 0) else None
    return canon, pods, big_r, sample_axis


def causal_order_ring(x, config=None, mesh=None):
    """Full causal order with the ring as the outer-loop driver.

    ``mesh`` defaults to the active ``jax.set_mesh`` mesh, else a flat ring
    over all devices; any shape is canonicalized by :func:`_canonical_mesh`
    (``model`` axis -> sample sharding, ``pod`` axis -> the two-level ring's
    pod level, everything else -> ring). ``config.ring_topology = (P, R)``
    overrides the pod/ring split explicitly — it must factor the row-shard
    count (``ConfigError`` otherwise); ``P=1`` forces the flat ring.
    Degenerate configurations (non-power-of-two pod or ring count) fall
    back to ``causal_order_scan`` — same order (and same dense/threshold
    inner evaluation), single shard.

    ``config.threshold`` selects the per-iteration evaluation: the dense
    messaging ring sweep (every live pair evaluated once, both endpoints
    credited), or the per-shard threshold state machine
    (``dist.ring._ring_threshold_body``) whose comparison savings compose
    with the ring's 1/(P*R*M) HBM/wire scaling. Either way the
    ``ParaLiNGAMResult`` counters are uniform with the host/scan drivers:
    per-iteration device-measured ``comparisons``/``rounds``/``converged``
    (analytic r(r-1)/2, 0, True for the dense sweep — measured on device
    from the live mask, not host bookkeeping) — plus the ring-only ``wire``
    surface: per-iteration ppermute-round counters (intra/cross x
    overlapped/sequential) aggregated into the hop/exchange/overlap model
    EXPERIMENTS.md quotes.
    """
    from repro.core.paralingam import (
        ConfigError,
        ParaLiNGAMConfig,
        _result_from_counters,
        _to_device,
        causal_order_scan,
    )

    cfg = config or ParaLiNGAMConfig()
    x = _to_device(x, cfg.dtype)
    p, n = x.shape
    want_pods = cfg.ring_topology[0] if cfg.ring_topology else None
    try:
        canon, pods, big_r, sample_axis = _canonical_mesh(mesh, n, want_pods)
    except ValueError as e:
        raise ConfigError(
            f"ring_topology={cfg.ring_topology} does not fit the device "
            f"mesh: {e}") from e
    if cfg.ring_topology and cfg.ring_topology[1] != big_r:
        raise ConfigError(
            f"ring_topology={cfg.ring_topology} does not fit the device "
            f"mesh: {pods} pods leave {big_r} ring shards")
    if (big_r & (big_r - 1)) or (pods & (pods - 1)):
        return causal_order_scan(x, cfg)

    from repro.kernels import ops as kops

    with tracing.span("repro.ring.prepare"):
        xn = normalize(x)
        c = cov_matrix(xn)
    with tracing.span("repro.dispatch"):
        run = _make_ring_order_fn(
            canon, sample_axis, p, n, next_pow2(max(cfg.min_bucket, 1)),
            backend=kops.select_backend(cfg), threshold=cfg.threshold,
            chunk=cfg.chunk, gamma0=float(cfg.gamma0),
            gamma_growth=float(cfg.gamma_growth), max_rounds=cfg.max_rounds,
        )
        # shard_map wants the context mesh to be the one it maps over, so
        # the canonical mesh stands in for the caller's ``jax.set_mesh``
        # mesh here.
        with jax.set_mesh(canon):
            order, comps_it, rounds_it, conv_it, hops_it = run(xn, c)
    return _result_from_counters(order, comps_it, rounds_it, conv_it, p,
                                 cfg.max_rounds, hops_it=hops_it,
                                 topology=(pods, big_r))
