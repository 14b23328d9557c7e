"""ParaLiNGAM (Algorithms 3-6, 9-10 of the paper), adapted to SPMD/TPU.

The paper's CUDA worker/scheduler design maps onto a 2-axis config surface
(see DESIGN.md Section 2 for the mechanism mapping):

``order_backend`` — which loop drives the p find-root -> update iterations:

  * ``host`` — the python outer loop: one find-root dispatch + ``int(root)``
               sync per iteration, numpy bucket re-gathers between them.
  * ``scan`` — the outer loop folded on-device: all p iterations in ONE
               dispatch over fixed-size masked buffers
               (``causal_order_scan``), stage compactions via device-side
               gathers — eliminating the host round-trips.
  * ``ring`` — the multi-device messaging ring
               (``dist.ring_order.causal_order_ring``): row blocks shard
               over the mesh's ring axis and circulate by ppermute, the
               samples axis shards over ``model`` with psum'd entropy
               moments, all p iterations device-resident.

``threshold`` — which evaluation each iteration runs (orthogonal):

  * ``False`` — the TPU-natural one-shot dense evaluation of the whole
                comparison matrix with messaging folded in (each residual
                entropy computed exactly once, both workers credited): the
                paper's "Block Compare" baseline *plus* messaging.
  * ``True``  — the paper's threshold mechanism (Sections 3.2-3.3): workers
                process comparison targets in fixed-size chunks inside a
                ``lax.while_loop``; a worker pauses when its partial score
                exceeds the adaptive bound gamma; gamma grows by factor
                ``gamma_growth`` when everyone is paused; the iteration
                terminates when every below-threshold worker has finished
                (Algorithm 6's condition). Device-measured comparison
                counts validate the paper's ~93% savings — uniformly
                reported across all three backends (the ring runs the state
                machine per shard with psum'd convergence).

Messaging is inherent to every combination: pair (i, j) is evaluated once
and both S[i] += min(0, I)^2 and S[j] += min(0, -I)^2 are applied
(Section 3.1).

Across outer iterations, the remaining set U shrinks; rows are compacted into
power-of-two *buckets* so each bucket size compiles once (<= log2 p
specializations) and the total search work is sum_r r^2 n, matching the
paper's per-iteration shrinking workers.

Exactness: identical causal orders to sequential DirectLiNGAM (asserted in
tests); the threshold path additionally returns the same root per iteration
as the dense path by the paper's Section 3.2 correctness argument.
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.covariance import (
    cov_matrix,
    normalize,
    update_cov,
    update_data,
)
from repro.core.pairwise import (
    dense_scores,
    fused_scores,
    pair_moments,
    pair_stat_matrix,
    row_entropies,
    scores_from_stats,
)
from repro.core.pairwise import residual_entropy_matrix as _hr_jnp
from repro.utils.schedule import make_schedule
from repro.utils.shapes import next_pow2


class ConfigError(ValueError):
    """A ``ParaLiNGAMConfig`` combination is contradictory or unknown.

    Raised at construction (and by :func:`resolve_order_backend`) instead of
    silently preferring one flag over another — the pre-redesign tangle where
    ``ring=True`` *overrode* ``method`` while ``method="threshold"`` +
    ``ring=True`` raised from deep inside the ring driver is exactly the bug
    class this type exists to kill."""


#: The order-driver enum: which loop recovers the causal order.
#:   ``host`` — the python outer loop (one find-root dispatch + ``int(root)``
#:              sync per iteration);
#:   ``scan`` — the device-resident staged scan (whole order in ONE jit);
#:   ``ring`` — the multi-device messaging ring driving all p iterations
#:              (``dist.ring_order.causal_order_ring``).
#: Orthogonal to ``threshold``: every backend runs either the dense
#: messaging evaluation (``threshold=False``) or the paper's threshold
#: state machine (``threshold=True``) per iteration.
ORDER_BACKENDS = ("host", "scan", "ring")

# The legacy method/ring spellings warn once per process, not once per
# config (configs are built per request on the serve path).
_legacy_order_warned = False


def _reset_legacy_order_warning() -> None:
    """Re-arm the one-shot legacy-spelling DeprecationWarning (tests)."""
    global _legacy_order_warned
    _legacy_order_warned = False


def _legacy_order_backend(order_backend: str, method, ring, threshold: bool):
    """One-release compatibility shim: map the retired ``method`` /
    ``ring`` flag pair onto the ``order_backend`` enum + orthogonal
    ``threshold`` bool. Returns ``(order_backend, threshold)``.

    The legacy semantics are preserved exactly: ``ring=True`` routed to the
    ring driver regardless of ``method`` (with ``method="threshold"`` now
    mapping to the implemented threshold-in-ring instead of raising);
    ``method="dense"`` *ignored* ``threshold``, so it maps to
    ``threshold=False``. Mixing the old and new spellings is ambiguous and
    refused."""
    global _legacy_order_warned
    if not _legacy_order_warned:
        warnings.warn(
            "ParaLiNGAMConfig(method=..., ring=...) is deprecated; use "
            "order_backend='host'|'scan'|'ring' with the orthogonal "
            "threshold flag (method='dense' -> order_backend='host', "
            "method='threshold' -> order_backend='host' + threshold=True, "
            "method='scan' -> order_backend='scan', ring=True -> "
            "order_backend='ring'). The legacy flags will be removed next "
            "release.",
            DeprecationWarning,
            stacklevel=4,
        )
        _legacy_order_warned = True
    if order_backend != "host":
        raise ConfigError(
            "pass either order_backend or the deprecated method/ring flags, "
            f"not both (got order_backend={order_backend!r}, "
            f"method={method!r}, ring={ring})"
        )
    if method not in (None, "dense", "threshold", "scan"):
        raise ConfigError(f"unknown method {method!r}")
    if ring:
        # ring=True took precedence over method; method="threshold" selects
        # the (now implemented) threshold-in-ring state machine.
        return "ring", threshold or method == "threshold"
    if method == "threshold":
        return "host", True
    if method == "scan":
        return "scan", threshold
    # method="dense" (or bare ring=False): the dense host driver, which
    # always ignored cfg.threshold.
    return "host", False


def resolve_order_backend(cfg) -> str:
    """Resolve a config's order driver to a concrete backend name, once per
    dispatch (mirrors ``kernels.ops.select_backend`` for score backends).
    Raises :class:`ConfigError` for names outside ``ORDER_BACKENDS``."""
    backend = getattr(cfg, "order_backend", "host")
    if backend not in ORDER_BACKENDS:
        raise ConfigError(
            f"order_backend={backend!r} is not one of {ORDER_BACKENDS}"
        )
    return backend


def _legacy_backend(score_backend: str, use_kernel, fused, caller: str) -> str:
    """One-release compatibility shim: map the retired ``use_kernel``/
    ``fused`` flag pair onto the ``score_backend`` enum (the 2x2 is exactly
    the four concrete backends). Mixing the old and new spellings is
    ambiguous and refused rather than guessed."""
    if use_kernel is None and fused is None:
        return score_backend
    warnings.warn(
        f"{caller}(use_kernel=..., fused=...) is deprecated; use "
        "score_backend='xla'|'xla_fused'|'pallas'|'pallas_fused' (or leave "
        "'auto'). The legacy flags will be removed next release.",
        DeprecationWarning,
        stacklevel=3,
    )
    if score_backend != "auto":
        raise ValueError(
            "pass either score_backend or the deprecated use_kernel/fused "
            f"flags, not both (got score_backend={score_backend!r}, "
            f"use_kernel={use_kernel}, fused={fused})"
        )
    return {
        (False, False): "xla",
        (False, True): "xla_fused",
        (True, False): "pallas",
        (True, True): "pallas_fused",
    }[(bool(use_kernel), bool(fused))]


@dataclass(frozen=True)
class ParaLiNGAMConfig:
    order_backend: str = "host"  # "host" | "scan" | "ring" — which loop
    #   drives the causal-order recovery (``ORDER_BACKENDS``): the python
    #   host loop (one find-root dispatch per iteration), the device-resident
    #   staged scan (whole order in ONE jit), or the multi-device messaging
    #   ring (``dist/ring_order.causal_order_ring``: row blocks shard over
    #   the mesh's ring axis, the samples axis over ``model`` with psum'd
    #   entropy moments; uses the active ``jax.set_mesh`` mesh, else all
    #   devices as a flat ring). Orthogonal to ``threshold`` — every backend
    #   supports both the dense and the thresholded per-iteration
    #   evaluation. Resolved once per dispatch by
    #   ``resolve_order_backend``; unknown names raise ``ConfigError``.
    method: str | None = None  # DEPRECATED -> order_backend ("dense" ->
    #   "host", "threshold" -> "host"+threshold, "scan" -> "scan")
    ring: bool | None = None  # DEPRECATED -> order_backend="ring"
    ring_topology: tuple | None = None  # (P, R) pod/ring split of the
    #   messaging ring's row shards (``order_backend="ring"`` only): P pods
    #   of R intra-pod shards run the two-level hop plan from
    #   ``utils.schedule.make_hier_plan`` — intra-pod hop every step,
    #   cross-pod exchange once per revolution. None derives the split from
    #   the mesh (its ``pod`` axis, else flat); (1, R) forces the flat ring.
    #   Both factors must be powers of two, and P*R must equal the mesh's
    #   row-shard count at dispatch (``ConfigError`` otherwise).
    # dense path
    block_j: int = 32  # j-block for the HR matrix (bounds the (p,bj,n) buffer)
    score_backend: str = "auto"  # "xla" | "xla_fused" | "pallas" |
    #   "pallas_fused" | "auto" — which formulation scores the comparison
    #   matrix (``kernels.ops.SCORE_BACKENDS``). ``xla*`` are the jnp
    #   oracles (square / fused triangular); ``pallas*`` the kernel routes
    #   (interpret-mode on CPU); ``auto`` resolves once per dispatch in
    #   ``kernels.ops.select_backend`` (fused kernel on TPU, square oracle
    #   elsewhere). Unknown names raise ``kernels.ops.BackendUnavailable``.
    use_kernel: bool | None = None  # DEPRECATED -> score_backend ("pallas*")
    fused: bool | None = None  # DEPRECATED -> score_backend ("*_fused")
    # threshold mechanism (paper Sections 3.2-3.3), orthogonal to the
    # order backend: run the comparison-saving threshold state machine
    # (gamma-growth, chunked pending comparisons, messaging credits) per
    # iteration instead of the dense evaluation — in the host loop, inside
    # the one-dispatch scan, or per ring shard with psum'd convergence.
    threshold: bool = False
    chunk: int = 16  # comparison targets processed per worker per round
    gamma0: float = 1e-5  # initial threshold (paper: "a small value")
    gamma_growth: float = 2.0  # the constant c of Algorithm 6 line 16
    max_rounds: int = 100_000
    # bucketed compaction of the remaining set U
    bucket: bool = True
    min_bucket: int = 32
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.method is not None or self.ring is not None:
            backend, thr = _legacy_order_backend(
                self.order_backend, self.method, self.ring, self.threshold
            )
            object.__setattr__(self, "order_backend", backend)
            object.__setattr__(self, "threshold", thr)
        if self.order_backend not in ORDER_BACKENDS:
            raise ConfigError(
                f"order_backend={self.order_backend!r} is not one of "
                f"{ORDER_BACKENDS}"
            )
        if self.ring_topology is not None:
            topo = tuple(self.ring_topology)
            if (len(topo) != 2
                    or any(not isinstance(v, int) or v < 1 or v & (v - 1)
                           for v in topo)):
                raise ConfigError(
                    f"ring_topology={self.ring_topology!r} must be a (pods, "
                    "ring) pair of power-of-two positive ints")
            if self.order_backend != "ring":
                raise ConfigError(
                    "ring_topology is only meaningful with "
                    f"order_backend='ring' (got {self.order_backend!r})")
            object.__setattr__(self, "ring_topology", topo)
        if self.use_kernel is None and self.fused is None:
            return
        object.__setattr__(
            self,
            "score_backend",
            _legacy_backend(self.score_backend, self.use_kernel, self.fused,
                            "ParaLiNGAMConfig"),
        )


@dataclass
class ParaLiNGAMResult:
    order: list[int]
    comparisons: int  # unordered pair evaluations actually performed
    comparisons_dense: int  # sum_r r(r-1)/2 — messaging-only baseline
    comparisons_serial: int  # sum_r r(r-1)  — DirectLiNGAM baseline
    rounds: int  # threshold-loop rounds (0 for dense)
    per_iteration: list[dict] = field(default_factory=list)
    converged: bool = True  # False iff any threshold loop hit max_rounds
    noise_var: np.ndarray | None = None  # Omega diagonal (set by ``fit``)
    diagnostics: object | None = None  # core.validate.DatasetDiagnostics
    #   when the fit ran with validate=True (admission guardrail record)
    wire: dict | None = None  # ring-backend only: device-measured ppermute
    #   round counters summed over the recovery — {"pods", "ring",
    #   "hops_intra", "hops_cross", "hops_overlapped", "seq_hops",
    #   "seq_cross_hops", "overlap_frac"} (see utils.schedule.HOP_* and
    #   HierPlan.hop_counts, whose analytic per-iteration model these
    #   validate). None for the host/scan drivers.

    @property
    def saving_vs_serial(self) -> float:
        return 1.0 - self.comparisons / max(self.comparisons_serial, 1)

    @property
    def saving_vs_messaging(self) -> float:
        return 1.0 - self.comparisons / max(self.comparisons_dense, 1)


# ---------------------------------------------------------------------------
# dense find-root
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("block_j", "backend"))
def _find_root_dense_impl(xn, c, mask, block_j: int, backend: str,
                          n_valid=None):
    """Concrete-backend dense evaluation (``backend`` already resolved —
    never ``"auto"`` here). All four backends honor both padding seams:
    ``n_valid`` rides into the kernels as the scalar-prefetched finalize
    denominator (raw moment sums are exact under zero-padded columns), into
    the jnp oracles as the ``stream_moments`` denominator."""
    if backend == "pallas_fused":
        from repro.kernels import ops as kops

        s = kops.score_vector(xn, c, mask, n_valid=n_valid)
        return jnp.argmin(s), s
    if backend == "xla_fused":
        s = fused_scores(xn, c, mask, block=min(block_j, xn.shape[0]),
                         n_valid=n_valid)
        return jnp.argmin(s), s
    hx = row_entropies(xn, mask, n_valid=n_valid)
    if backend == "pallas":
        from repro.kernels import ops as kops

        hr = kops.residual_entropy_matrix(xn, c, n_valid=n_valid)
    else:
        hr = _hr_jnp(xn, c, block_j, n_valid=n_valid)
    stat = pair_stat_matrix(hx, hr)
    s = scores_from_stats(stat, mask)
    return jnp.argmin(s), s


def find_root_dense(xn, c, mask, block_j: int = 32, use_kernel=None,
                    fused=None, n_valid=None, *, score_backend: str = "auto"):
    """One-shot masked dense evaluation. Returns (root_idx, scores).

    ``score_backend`` selects the formulation (``kernels.ops.SCORE_BACKENDS``):
    the square jnp oracle (``"xla"``), the fused triangular jnp path
    (``"xla_fused"`` — each unordered block pair evaluated once, messaging
    credit applied in the same pass, no p x p HR intermediate), or the Pallas
    kernel routes (``"pallas"``/``"pallas_fused"``; interpret mode off-TPU).
    All produce identical scores up to f32 summation order, on padded
    (``n_valid``, the ``pairwise.stream_moments`` seam) and unpadded data
    alike — the old silent kernel->jnp downgrade on ``n_valid`` dispatches is
    gone. ``use_kernel``/``fused`` are the deprecated flag spellings."""
    backend = _legacy_backend(score_backend, use_kernel, fused,
                              "find_root_dense")
    from repro.kernels import ops as kops

    backend = kops.select_backend(backend, n_valid=n_valid)
    return _find_root_dense_impl(xn, c, mask, block_j=block_j,
                                 backend=backend, n_valid=n_valid)


# ---------------------------------------------------------------------------
# threshold find-root (paper Algorithms 4-6 in SPMD form)
# ---------------------------------------------------------------------------


def _find_root_threshold_impl(
    xn,
    c,
    mask,
    gamma0,
    gamma_growth,
    chunk: int = 16,
    max_rounds: int = 100_000,
    n_valid=None,
):
    """Threshold-mechanism find-root state machine (shared by the jitted
    standalone ``find_root_threshold`` and the device-resident scan driver).
    Returns (root, scores, comparisons, rounds, converged).

    One while-loop round either (a) lets every *active* worker process its
    next pending chunk of comparison targets — crediting both pair endpoints
    (messaging) and dedup-ing simultaneous mutual comparisons exactly as the
    paper's scheduler line 22 / atomicCAS flags do — or (b) grows gamma when
    no worker is below threshold (Algorithm 6 lines 15-17). ``converged`` is
    False iff the loop was cut off by ``max_rounds`` before Algorithm 6's
    termination condition held (scores may then be incomplete).

    A mask with fewer than two live rows (padded buffers in the batched-fit
    path can drain entirely) has no pairs to process: the loop is skipped —
    Algorithm 6's condition can never hold, so without the guard the gamma
    growth branch would spin to ``max_rounds`` — and the iteration reports
    converged with zero comparisons.
    """
    m, _ = xn.shape
    # The gathered-chunk evaluation is the shared ``pairwise.pair_moments``
    # on every backend (no Pallas formulation exists for a gather layout;
    # ``kernels.ops.pair_moments`` is the seam to add one later).
    # Round the chunk down to a divisor of m (m is static at trace time) so
    # non-power-of-two row counts (bucket=False with awkward p) still reshape
    # into whole chunks; worst case chunk=1 == the paper's one-at-a-time worker.
    chunk = max(1, min(chunk, m))
    while m % chunk:
        chunk -= 1
    nc = m // chunk
    idx = jnp.arange(m)
    pair_valid = mask[:, None] & mask[None, :] & ~jnp.eye(m, dtype=bool)
    has_pairs = jnp.any(pair_valid)
    hx = row_entropies(xn, mask, n_valid=n_valid)

    d0 = ~pair_valid  # done := not a live pair (diag + dead rows/cols)
    s0 = jnp.where(mask, 0.0, jnp.inf)
    state0 = dict(
        s=s0,
        d=d0,
        gamma=jnp.asarray(gamma0, xn.dtype),
        comparisons=jnp.asarray(0, jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32),
        rounds=jnp.asarray(0, jnp.int32),
        terminal=jnp.asarray(False),
    )

    def finished_of(d):
        return jnp.all(d, axis=1)  # all pairs done (dead pairs pre-marked)

    def terminal_of(s, d, gamma):
        below = (s < gamma) & mask
        fin = finished_of(d)
        # Algorithm 6: finish iff some below-threshold worker is finished and
        # *no* below-threshold worker is unfinished.
        return jnp.any(below & fin) & ~jnp.any(below & ~fin)

    def round_body(st):
        s, d, gamma = st["s"], st["d"], st["gamma"]
        fin = finished_of(d)
        active = (s < gamma) & ~fin & mask

        def do_round(_):
            pending = ~d & pair_valid  # (m, m)
            pend_chunk = jnp.any(pending.reshape(m, nc, chunk), axis=2)  # (m, nc)
            ci = jnp.argmax(pend_chunk, axis=1)  # first pending chunk per worker
            cols = ci[:, None] * chunk + jnp.arange(chunk)[None, :]  # (m, B)
            xj = xn[cols.reshape(-1)].reshape(m, chunk, -1)
            c_vals = jnp.take_along_axis(c, cols, axis=1)
            hr_fwd, hr_rev = pair_moments(xn, c_vals, xj, n_valid=n_valid)
            hx_j = hx[cols]
            stat = (hx_j - hx[:, None]) + (hr_fwd - hr_rev)  # I(i, j): (m, B)

            proc = (
                active[:, None]
                & jnp.take_along_axis(pending, cols, axis=1)
            )
            rows = jnp.broadcast_to(idx[:, None], cols.shape)
            # Dedup simultaneous mutual comparisons (paper Alg. 6 line 22):
            # if j also proposes (j, i) this round, the lower index keeps it.
            prop = jnp.zeros((m, m), bool).at[rows, cols].max(proc)
            partner_also = jnp.take_along_axis(prop.T, cols, axis=1)
            keep = proc & (~partner_also | (rows < cols))

            fwd_contrib = jnp.where(keep, jnp.square(jnp.minimum(0.0, stat)), 0.0)
            rev_contrib = jnp.where(keep, jnp.square(jnp.minimum(0.0, -stat)), 0.0)
            s_new = s + jnp.sum(fwd_contrib, axis=1)
            s_new = s_new.at[cols.reshape(-1)].add(rev_contrib.reshape(-1))
            d_new = d.at[rows, cols].max(keep)
            d_new = d_new.at[cols, rows].max(keep)
            comps = jnp.sum(keep).astype(st["comparisons"].dtype)
            return s_new, d_new, gamma, comps

        def grow_gamma(_):
            return s, d, gamma * gamma_growth, jnp.asarray(0, st["comparisons"].dtype)

        s2, d2, g2, comps = jax.lax.cond(jnp.any(active), do_round, grow_gamma, None)
        return dict(
            s=s2,
            d=d2,
            gamma=g2,
            comparisons=st["comparisons"] + comps,
            rounds=st["rounds"] + 1,
            terminal=terminal_of(s2, d2, g2),
        )

    def cond(st):
        return ~st["terminal"] & (st["rounds"] < max_rounds) & has_pairs

    final = jax.lax.while_loop(cond, round_body, state0)
    root = jnp.argmin(jnp.where(mask, final["s"], jnp.inf))
    # cond exits because terminal held (converged), because there were no
    # live pairs to begin with (trivially converged), or because rounds hit
    # max_rounds with terminal still False (truncated).
    return (root, final["s"], final["comparisons"], final["rounds"],
            final["terminal"] | ~has_pairs)


@partial(jax.jit, static_argnames=("chunk", "max_rounds"))
def find_root_threshold(
    xn,
    c,
    mask,
    gamma0: float,
    gamma_growth: float,
    chunk: int = 16,
    max_rounds: int = 100_000,
    n_valid=None,
):
    """Jitted threshold-mechanism find-root.
    Returns (root, scores, comparisons, rounds, converged) — see
    ``_find_root_threshold_impl`` for the round semantics; ``converged`` is
    False when ``max_rounds`` truncated the loop (Algorithm 6's termination
    condition never held, so the winning score may be partial)."""
    return _find_root_threshold_impl(
        xn, c, mask, gamma0, gamma_growth,
        chunk=chunk, max_rounds=max_rounds, n_valid=n_valid,
    )


# ---------------------------------------------------------------------------
# full causal-order driver (Algorithm 3)
# ---------------------------------------------------------------------------


@jax.jit
def _update_iteration(xn, c, root, mask, n_valid=None):
    """UpdateData + UpdateCovMat (Algorithms 7-8) and drop root from U."""
    xn2 = update_data(xn, c, root, mask, n_valid=n_valid)
    c2 = update_cov(c, root, mask)
    mask2 = mask & (jnp.arange(xn.shape[0]) != root)
    return xn2, c2, mask2


def _scan_stages(p: int, min_bucket: int) -> list[tuple[int, int]]:
    """Static stage plan: (buffer size m, iteration count) pairs for the
    single-shard scan driver — now just the R=1 slice of the unified
    topology-aware :func:`repro.utils.schedule.make_schedule` (the ring
    driver consumes the same object with its ring size, so the two plans
    cannot drift)."""
    return list(make_schedule(p, min_bucket).stages)


def _scan_order_impl(xn, c, gamma0, gamma_growth, block_j: int = 32,
                     backend: str = "xla",
                     min_bucket: int = 32, threshold: bool = False,
                     chunk: int = 16, max_rounds: int = 100_000,
                     mask0=None, n_valid=None):
    """Device-resident outer loop: all p find-root -> update iterations in
    ONE dispatch, with no host round-trips.

    The loop is staged on the same power-of-two schedule as the host driver's
    buckets, but entirely on-device: each stage is a ``lax.fori_loop`` over
    fixed-size mask-based buffers, and the <= log2(p) stage transitions
    compact live rows with a device-side ``jnp.nonzero(size=m)`` gather (the
    host driver instead syncs ``int(root)`` and re-gathers from numpy every
    one of the p iterations). Work profile and per-iteration float ops match
    the bucketed host driver exactly — padded rows are masked out of every
    reduction — so the returned order is identical.

    ``threshold=True`` replaces the dense evaluation with the threshold
    state machine (``_find_root_threshold_impl``'s ``lax.while_loop`` over
    rounds: gamma growth, chunked pending-comparison processing, messaging
    credits to both endpoints, mutual-comparison dedup) *inside* each
    ``fori_loop`` iteration — its (m, m) done matrix and (m,) score buffer
    live and die within the iteration, while the carried (m, n)/(m, m)
    data buffers survive the stage compactions. One dispatch then delivers
    both the paper's ~93% comparison savings and the dispatch amortization.

    Padded-buffer seam (the batched frontend): ``mask0`` marks the initially
    live rows (None -> all live; dead rows must be zero in ``xn``) and
    ``n_valid`` the valid sample-column count (``pairwise.stream_moments``
    contract). The stage plan stays static — a dataset with fewer live rows
    simply drains early: once its mask is empty the remaining iterations
    retire nothing and write garbage order entries past position
    ``sum(mask0) - 1`` (``adjacency.complete_order`` sanitizes them). Live
    counts are therefore *device-derived* (``sum(mask)``) rather than the
    static ``p - iteration`` bookkeeping, which also makes the whole driver
    vmap-safe over a batch of differently-masked datasets.

    Returns ``(order, comps_it, rounds_it, conv_it)``: the causal order plus
    per-iteration device-measured comparison counts, threshold-round counts
    and convergence flags (for the dense evaluation these are the analytic
    r(r-1)/2, 0 and True — same contract, no host bookkeeping)."""
    p = xn.shape[0]
    cdtype = jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32
    order = jnp.zeros((p,), jnp.int32)
    comps_it = jnp.zeros((p,), cdtype)
    rounds_it = jnp.zeros((p,), jnp.int32)
    conv_it = jnp.ones((p,), bool)
    if p == 1:
        return order, comps_it, rounds_it, conv_it

    idx_g = jnp.arange(p, dtype=jnp.int32)  # local row -> global variable id
    xb, cb = xn, c
    mloc = jnp.ones((p,), bool) if mask0 is None else mask0
    m_cur = p
    pos = 0
    for m, cnt in _scan_stages(p, min_bucket):
        if m != m_cur:
            # Compaction: pack live rows first; the live count is derived on
            # device (== the static p - pos when mask0 is None, fewer when a
            # padded dataset started with dead rows).
            live = jnp.sum(mloc)
            sel = jnp.nonzero(mloc, size=m, fill_value=0)[0].astype(jnp.int32)
            idx_g = idx_g[sel]
            xb = xb[sel]
            cb = cb[sel][:, sel]
            mloc = jnp.arange(m) < live
            m_cur = m

        def body(k, st, idx_g=idx_g, pos=pos, m=m):
            xb, cb, ml, order, comps_it, rounds_it, conv_it = st
            if threshold:
                root_l, _, comps, rounds, conv = _find_root_threshold_impl(
                    xb, cb, ml, gamma0, gamma_growth,
                    chunk=min(chunk, m), max_rounds=max_rounds, n_valid=n_valid,
                )
            else:
                root_l, _ = _find_root_dense_impl(
                    xb, cb, ml, block_j=min(block_j, m),
                    backend=backend, n_valid=n_valid,
                )
                r = jnp.sum(ml).astype(cdtype)  # live rows this iteration
                comps = r * (r - 1) // 2
                rounds = jnp.asarray(0, jnp.int32)
                conv = jnp.asarray(True)
            it = pos + k
            order = order.at[it].set(idx_g[root_l])
            comps_it = comps_it.at[it].set(comps)
            rounds_it = rounds_it.at[it].set(rounds.astype(jnp.int32))
            conv_it = conv_it.at[it].set(conv)
            xb2 = update_data(xb, cb, root_l, ml, n_valid=n_valid)
            cb2 = update_cov(cb, root_l, ml)
            ml2 = ml & (jnp.arange(m) != root_l)
            return xb2, cb2, ml2, order, comps_it, rounds_it, conv_it

        st = (xb, cb, mloc, order, comps_it, rounds_it, conv_it)
        xb, cb, mloc, order, comps_it, rounds_it, conv_it = jax.lax.fori_loop(
            0, cnt, body, st
        )
        pos += cnt

    # One live row remains (for a full buffer); no find-root needed (matches
    # the host driver). An already-drained padded buffer writes garbage here,
    # past its valid prefix.
    order = order.at[p - 1].set(idx_g[jnp.argmax(mloc)])
    return order, comps_it, rounds_it, conv_it


# One dispatch for the whole order. Nothing is donated: no output has the
# shape of ``xn`` or ``c``, so XLA could alias neither and donation would
# only warn.
_scan_order = jax.jit(
    _scan_order_impl,
    static_argnames=(
        "block_j", "backend", "min_bucket",
        "threshold", "chunk", "max_rounds",
    ),
)


def _result_from_counters(order, comps_it, rounds_it, conv_it, p: int,
                          max_rounds: int, stacklevel: int = 3,
                          hops_it=None,
                          topology: tuple | None = None) -> ParaLiNGAMResult:
    """Host-side ParaLiNGAMResult from the device-measured per-iteration
    counters of the scan/fit pipeline (the one host readback point).
    ``stacklevel`` points the max_rounds warning at the caller of the public
    entry point (3 = one public frame above this helper). The ring driver
    additionally passes ``hops_it`` — the (p, 4) per-iteration ppermute
    round counters (``utils.schedule.HOP_*``) — and its (pods, ring)
    ``topology``; they aggregate into ``ParaLiNGAMResult.wire`` and ride
    each ``per_iteration`` record as a ``hops`` tuple."""
    comps_np = np.asarray(comps_it)
    rounds_np = np.asarray(rounds_it)
    conv_np = np.asarray(conv_it)
    hops_np = None if hops_it is None else np.asarray(hops_it)
    per_iter = [
        {
            "r": r,
            "comparisons": int(comps_np[i]),
            "rounds": int(rounds_np[i]),
            "converged": bool(conv_np[i]),
            **({} if hops_np is None
               else {"hops": tuple(int(v) for v in hops_np[i])}),
        }
        for i, r in enumerate(range(p, 1, -1))
    ]
    wire = None
    if hops_np is not None:
        from repro.utils.schedule import (
            HOP_CROSS_OVL, HOP_CROSS_SEQ, HOP_INTRA_OVL, HOP_INTRA_SEQ,
        )

        tot = hops_np[: max(p - 1, 0)].sum(axis=0)
        io, is_ = int(tot[HOP_INTRA_OVL]), int(tot[HOP_INTRA_SEQ])
        co, cs = int(tot[HOP_CROSS_OVL]), int(tot[HOP_CROSS_SEQ])
        all_hops = io + is_ + co + cs
        wire = {
            "pods": int(topology[0]) if topology else 1,
            "ring": int(topology[1]) if topology else 1,
            "hops_intra": io + is_,
            "hops_cross": co + cs,
            "hops_overlapped": io + co,
            "seq_hops": is_ + cs,
            "seq_cross_hops": cs,
            "overlap_frac": (io + co) / all_hops if all_hops else 0.0,
        }
    converged = bool(conv_np.all())
    if not converged:
        warnings.warn(
            f"find_root_threshold hit max_rounds={max_rounds} in "
            f"{int(p - 1 - conv_np[: p - 1].sum())} of {p - 1} scan iterations; "
            "scores may be incomplete (raise max_rounds or gamma_growth)",
            stacklevel=stacklevel,
        )
    comps_dense = sum(r * (r - 1) // 2 for r in range(2, p + 1))
    return ParaLiNGAMResult(
        order=[int(v) for v in np.asarray(order)],
        comparisons=int(comps_np.sum()),
        comparisons_dense=comps_dense,
        comparisons_serial=2 * comps_dense,
        rounds=int(rounds_np.sum()),
        per_iteration=per_iter,
        converged=converged,
        wire=wire,
    )


def causal_order_scan(x, config: ParaLiNGAMConfig | None = None) -> ParaLiNGAMResult:
    """Full causal order in ONE device dispatch (vs the host driver's p
    find-root dispatches with an ``int(root)`` sync + bucket re-gather each).

    Same bucketed work profile as the host driver, zero host round-trips:
    the win is every iteration's dispatch + sync latency — exactly the
    overhead the paper burns down by keeping all workers resident on the
    device across the whole recovery. With ``cfg.threshold`` the resident
    loop runs the threshold state machine per iteration, and the reported
    ``comparisons``/``rounds``/``per_iteration`` come from device-side
    counters measured inside the dispatch."""
    cfg = config or ParaLiNGAMConfig()
    from repro.kernels import ops as kops

    backend = kops.select_backend(cfg)
    x = jnp.asarray(x, cfg.dtype)
    p = x.shape[0]
    xn = normalize(x)
    c = cov_matrix(xn)
    order, comps_it, rounds_it, conv_it = _scan_order(
        xn, c,
        jnp.asarray(cfg.gamma0, cfg.dtype), jnp.asarray(cfg.gamma_growth, cfg.dtype),
        block_j=min(cfg.block_j, p), backend=backend,
        min_bucket=cfg.min_bucket,
        threshold=cfg.threshold, chunk=cfg.chunk, max_rounds=cfg.max_rounds,
    )
    return _result_from_counters(order, comps_it, rounds_it, conv_it, p,
                                 cfg.max_rounds)


def causal_order(x, config: ParaLiNGAMConfig | None = None) -> ParaLiNGAMResult:
    """ParaLiNGAM step 1: full causal order over ``x: (p, n)`` raw samples."""
    cfg = config or ParaLiNGAMConfig()
    driver = resolve_order_backend(cfg)
    if driver == "ring":
        from repro.dist.ring_order import causal_order_ring

        return causal_order_ring(x, cfg)
    if driver == "scan":
        return causal_order_scan(x, cfg)
    from repro.kernels import ops as kops

    backend = kops.select_backend(cfg)
    x = jnp.asarray(x, cfg.dtype)
    p = x.shape[0]

    xn = normalize(x)
    c = cov_matrix(xn)  # Algorithm 3 lines 3-4 (parallel normalize + cov)
    mask = jnp.ones((p,), bool)

    order: list[int] = []
    total_comps = 0
    total_rounds = 0
    comps_dense = 0
    comps_serial = 0
    converged_all = True
    per_iter: list[dict] = []
    mask_np = np.ones((p,), bool)

    for _ in range(p):
        live = np.flatnonzero(mask_np)
        r = len(live)
        if r == 1:
            order.append(int(live[0]))
            break
        comps_dense += r * (r - 1) // 2
        comps_serial += r * (r - 1)

        if cfg.bucket:
            m = max(cfg.min_bucket, next_pow2(r))
            m = min(m, next_pow2(p))
            idx_pad = np.full((m,), live[0], np.int32)
            idx_pad[:r] = live
            maskb = np.zeros((m,), bool)
            maskb[:r] = True
            idx_pad_j = jnp.asarray(idx_pad)
            xb = jnp.take(xn, idx_pad_j, axis=0)
            cb = jnp.take(jnp.take(c, idx_pad_j, axis=0), idx_pad_j, axis=1)
            mb = jnp.asarray(maskb)
        else:
            idx_pad = np.arange(p, dtype=np.int32)
            xb, cb, mb = xn, c, mask

        if not cfg.threshold:
            root_local, _ = _find_root_dense_impl(
                xb, cb, mb, block_j=min(cfg.block_j, xb.shape[0]),
                backend=backend,
            )
            iter_comps = r * (r - 1) // 2
            iter_rounds = 0
            iter_conv = True
        else:
            chunk = min(cfg.chunk, xb.shape[0])
            root_local, _, comps, rounds, conv = find_root_threshold(
                xb, cb, mb, cfg.gamma0, cfg.gamma_growth,
                chunk=chunk, max_rounds=cfg.max_rounds,
            )
            iter_comps = int(comps)
            iter_rounds = int(rounds)
            iter_conv = bool(conv)
            if not iter_conv:
                warnings.warn(
                    f"find_root_threshold hit max_rounds={cfg.max_rounds} at "
                    f"iteration {len(order)} (r={r}); scores may be incomplete "
                    "(raise max_rounds or gamma_growth)",
                    stacklevel=2,
                )

        root = int(idx_pad[int(root_local)])
        order.append(root)
        total_comps += iter_comps
        total_rounds += iter_rounds
        converged_all &= iter_conv
        per_iter.append(
            {"r": r, "comparisons": iter_comps, "rounds": iter_rounds,
             "converged": iter_conv}
        )

        xn, c, mask = _update_iteration(xn, c, jnp.asarray(root), mask)
        mask_np[root] = False

    return ParaLiNGAMResult(
        order=order,
        comparisons=total_comps,
        comparisons_dense=comps_dense,
        comparisons_serial=comps_serial,
        rounds=total_rounds,
        per_iteration=per_iter,
        converged=converged_all,
    )


# ---------------------------------------------------------------------------
# one-dispatch fit (order + adjacency fused) and the batched frontend
# ---------------------------------------------------------------------------


def _pipeline_impl(x, gamma0, gamma_growth, n_valid, mask0, *,
                   adjacency: bool, threshold: bool, block_j: int,
                   backend: str, min_bucket: int,
                   chunk: int, max_rounds: int, prune_below: float):
    """The whole estimator as ONE traced pipeline over raw samples
    ``x: (p, n)``: normalize -> covariance -> staged causal-order scan ->
    (optionally) phase-2 adjacency — no host round-trip anywhere, which is
    what lets ``fit`` be a single dispatch and ``fit_batch`` vmap the whole
    thing over a batch of datasets.

    Returns ``(order, comps_it, rounds_it, conv_it)`` plus ``(b, omega)``
    when ``adjacency`` (phase 2 consumes the *raw* x and the completed order
    permutation, exactly like the numpy oracle — see ``core.adjacency``)."""
    from repro.core.adjacency import adjacency_from_order, complete_order

    xn = normalize(x, n_valid=n_valid)
    if mask0 is not None:
        xn = jnp.where(mask0[:, None], xn, 0.0)  # dead rows exactly zero
    c = cov_matrix(xn, n_valid=n_valid)
    order, comps_it, rounds_it, conv_it = _scan_order_impl(
        xn, c, gamma0, gamma_growth, block_j=block_j, backend=backend,
        min_bucket=min_bucket, threshold=threshold, chunk=chunk,
        max_rounds=max_rounds, mask0=mask0, n_valid=n_valid,
    )
    if not adjacency:
        return order, comps_it, rounds_it, conv_it
    perm = order if mask0 is None else complete_order(order, mask0)
    b, omega = adjacency_from_order(
        x, perm, mask=mask0, n_valid=n_valid, prune_below=prune_below
    )
    return order, comps_it, rounds_it, conv_it, b, omega


@lru_cache(maxsize=None)
def _pipeline_fn(batched: bool, rules, **static):
    """Cached jit of ``_pipeline_impl`` (vmapped over the leading dataset
    axis when ``batched``). ``rules`` is a hashable ``ShardingRules`` whose
    batch axes the (B, p, n) input is constrained to — the ``dist`` seam that
    spreads a request batch over the ``"data"`` mesh axis."""

    def run(x, gamma0, gamma_growth, n_valid, mask0):
        f = partial(_pipeline_impl, **static)
        if not batched:
            return f(x, gamma0, gamma_growth, n_valid, mask0)
        if rules is not None:
            x = rules.act(x, "lingam_batch")  # batch-dim constraint only
        axes = (0, None, None,
                None if n_valid is None else 0,
                None if mask0 is None else 0)
        return jax.vmap(f, in_axes=axes)(x, gamma0, gamma_growth, n_valid, mask0)

    return jax.jit(run)


# Host-side estimator dispatch counters, threaded up into the serving stats
# surface (``serve.async_engine.AsyncLingamEngine.stats``).
#
#   "kernel_bypass"  — dispatches where a kernel backend was requested but a
#     jnp formulation ran instead. Since the moments redesign every backend
#     serves every seam (``n_valid``, masks, batching), so a bypass is a BUG,
#     not a capability gap: nothing increments it anymore, and the engine
#     suites assert it stays 0. The counter survives as the tripwire.
#   "auto_downgrade" — dispatches where ``score_backend="auto"`` resolved to
#     a jnp backend (off-TPU platform policy; see
#     ``kernels.ops.select_backend``). Expected off accelerators; surfaced
#     in ``AsyncLingamEngine.stats()`` so a deployment can tell "kernels
#     were never requested" from "kernels silently unavailable". Replaces
#     the old warn-once RuntimeWarning.
dispatch_stats: dict = {"kernel_bypass": 0, "auto_downgrade": 0}
# N submitter + dispatcher-replica threads all funnel through _bump_stat;
# the += races without this (lost increments under the GIL's bytecode-level
# interleaving).
_dispatch_stats_mu = threading.Lock()


def reset_dispatch_stats() -> None:
    """Zero ``dispatch_stats`` (tests). Thread-safe against concurrent
    dispatches."""
    with _dispatch_stats_mu:
        for k in dispatch_stats:
            dispatch_stats[k] = 0


def dispatch_stats_snapshot() -> dict:
    """Consistent point-in-time copy of ``dispatch_stats`` (the live dict
    may be mid-update in another thread)."""
    with _dispatch_stats_mu:
        return dict(dispatch_stats)


def _bump_stat(key: str, delta: int = 1) -> None:
    """Thread-safe ``dispatch_stats`` increment."""
    with _dispatch_stats_mu:
        dispatch_stats[key] += delta


def _note_backend(cfg: ParaLiNGAMConfig, backend: str) -> None:
    """Record dispatch-routing telemetry for a resolved backend choice:
    an ``"auto"`` request landing on a jnp formulation counts as an
    auto-downgrade (platform policy, not an error — see
    ``dispatch_stats``)."""
    if cfg.score_backend == "auto" and backend.startswith("xla"):
        _bump_stat("auto_downgrade")


def _pipeline_call(x, cfg: ParaLiNGAMConfig, *, adjacency: bool,
                   batched: bool, n_valid=None, mask0=None, rules=None,
                   prune_below: float = 0.0):
    """The resolved backend, the jitted pipeline and its arguments for one
    ``fit``/``fit_batch`` dispatch."""
    from repro.kernels import ops as kops

    backend = kops.select_backend(cfg, n_valid=n_valid, batched=batched)
    fn = _pipeline_fn(
        batched, rules if batched else None,
        adjacency=adjacency,
        threshold=cfg.threshold,
        block_j=cfg.block_j, backend=backend,
        min_bucket=cfg.min_bucket, chunk=cfg.chunk, max_rounds=cfg.max_rounds,
        prune_below=prune_below,
    )
    args = (
        jnp.asarray(x, cfg.dtype),
        jnp.asarray(cfg.gamma0, cfg.dtype), jnp.asarray(cfg.gamma_growth, cfg.dtype),
        n_valid, mask0,
    )
    return backend, fn, args


def _run_pipeline(x, cfg: ParaLiNGAMConfig, **kw):
    backend, fn, args = _pipeline_call(x, cfg, **kw)
    _note_backend(cfg, backend)
    return fn(*args)


def lower_fit(x, config: ParaLiNGAMConfig | None = None,
              prune_below: float = 0.0):
    """The one-dispatch program :func:`fit` runs on ``x`` under ``config``,
    lowered (a ``jax.stages.Lowered``). ``.compile().as_text()`` shows what
    the device runs, for example whether the score kernel is a native TPU
    custom call. That compile is separate from ``fit``'s own, which reuses
    it only through the persistent compilation cache. The ring driver is
    several dispatches and has no such single program."""
    cfg = config or ParaLiNGAMConfig()
    if resolve_order_backend(cfg) == "ring":
        raise ConfigError("lower_fit covers the one-dispatch pipeline; the "
                          "ring driver has no single program")
    _, fn, args = _pipeline_call(x, cfg, adjacency=True, batched=False,
                                 prune_below=prune_below)
    return fn.lower(*args)


def fit(x, config: ParaLiNGAMConfig | None = None, prune_below: float = 0.0,
        *, validate: bool = False):
    """Full DirectLiNGAM pipeline: causal order (step 1) + causal strengths B
    and noise variances (step 2). Returns ``(result, B)`` with ``B`` a (p, p)
    device array and ``result.noise_var`` the Omega diagonal.

    Both phases run device-resident in ONE jit dispatch (normalize ->
    covariance -> staged order scan -> Cholesky adjacency) — the host sees
    nothing until the final result readback. The order scan runs the dense
    or threshold inner evaluation per ``config.threshold``; the host drivers
    remain available via :func:`causal_order` +
    ``core.adjacency.estimate_adjacency``. With ``order_backend="ring"`` the
    order comes from the multi-device ring driver and phase 2 is a second
    (still device-side) dispatch.

    ``validate=True`` runs the :mod:`repro.core.validate` admission checks
    first — NaN/Inf cells, constant or duplicate variables, p > n rank
    deficiency raise a typed ``DatasetError`` *before* any device work, and
    the clean diagnostics land in ``result.diagnostics``."""
    cfg = config or ParaLiNGAMConfig()
    diag = None
    if validate:
        from repro.core.validate import require_valid

        diag = require_valid(x)
    if resolve_order_backend(cfg) == "ring":
        from repro.core.adjacency import adjacency_from_order_jit

        result = causal_order(x, cfg)
        b, omega = adjacency_from_order_jit(
            jnp.asarray(x, cfg.dtype),
            jnp.asarray(result.order, jnp.int32),
            prune_below=prune_below,
        )
        result.noise_var = np.asarray(omega)
        result.diagnostics = diag
        return result, b
    p = np.shape(x)[0]
    order, comps_it, rounds_it, conv_it, b, omega = _run_pipeline(
        x, cfg, adjacency=True, batched=False, prune_below=prune_below,
    )
    result = _result_from_counters(order, comps_it, rounds_it, conv_it, p,
                                   cfg.max_rounds)
    result.noise_var = np.asarray(omega)
    result.diagnostics = diag
    return result, b


@dataclass
class BatchFitResult:
    """Batched estimator outputs, one leading dataset axis everywhere.

    All fields are *device* arrays — nothing syncs to the host until the
    caller reads them (so a serving layer can keep results resident or
    ship them elsewhere). ``orders[i]`` is valid up to the i-th dataset's
    live-row count (the serve engine slices); ``comparisons``/``rounds``
    are per-iteration device counters (sum for totals), ``converged`` is
    per-iteration threshold convergence (``all`` for the dataset verdict).
    ``b``/``noise_var`` are None for order-only runs."""

    orders: jax.Array  # (B, p) int32
    comparisons: jax.Array  # (B, p)
    rounds: jax.Array  # (B, p) int32
    converged: jax.Array  # (B, p) bool
    b: jax.Array | None = None  # (B, p, p)
    noise_var: jax.Array | None = None  # (B, p)


def _coerce_batch(xs, cfg: ParaLiNGAMConfig, n_valid, mask, caller: str):
    """Shared frontend validation of the batched entry points: reject ring
    configs (no batched ring form — the batch axis shards via ``rules``),
    coerce the (B, p, n) stack and the per-dataset padding aux arrays."""
    if resolve_order_backend(cfg) == "ring":
        raise ConfigError(
            f"{caller} runs the vmapped scan pipeline; the ring driver has "
            "no batched form yet — use order_backend='host'|'scan' (shard "
            "the batch axis via `rules` instead) or per-dataset fit() for "
            "the ring"
        )
    xs = jnp.asarray(xs, cfg.dtype)
    if xs.ndim != 3:
        raise ValueError(f"{caller} wants (B, p, n), got {xs.shape}")
    nv = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
    if nv is not None and nv.ndim == 0:
        nv = jnp.broadcast_to(nv, (xs.shape[0],))
    mk = None if mask is None else jnp.asarray(mask, bool)
    return xs, nv, mk


def fit_batch(xs, config: ParaLiNGAMConfig | None = None, *, n_valid=None,
              mask=None, rules=None, prune_below: float = 0.0) -> BatchFitResult:
    """Batched one-dispatch DirectLiNGAM over ``xs: (B, p, n)`` — the same
    fused normalize -> order-scan -> adjacency pipeline as :func:`fit`,
    vmapped over the leading dataset axis so B problems share one dispatch
    (and one compiled executable per padded ``(p, n)`` shape bucket — the
    dispatch-amortization the serve engine is built on).

    ``n_valid`` ((B,) or scalar) and ``mask`` ((B, p) bool) mark the valid
    sample columns / live variable rows of shape-padded datasets (zero-pad
    the data; see ``serve.lingam_engine.pad_dataset``). ``rules`` is an
    optional ``dist.sharding.ShardingRules`` whose batch axes shard the
    dataset axis over the mesh (``make_rules(cfg, mesh)`` with a ``"data"``
    axis); orders are bit-identical to the unsharded dispatch."""
    cfg = config or ParaLiNGAMConfig()
    xs, nv, mk = _coerce_batch(xs, cfg, n_valid, mask, "fit_batch")
    order, comps, rounds, conv, b, omega = _run_pipeline(
        xs, cfg, adjacency=True, batched=True, n_valid=nv, mask0=mk,
        rules=rules, prune_below=prune_below,
    )
    return BatchFitResult(orders=order, comparisons=comps, rounds=rounds,
                          converged=conv, b=b, noise_var=omega)


@dataclass
class CompiledFitBatch:
    """AOT-compiled :func:`fit_batch` executable for ONE ``(batch, p, n)``
    bucket shape (see :func:`aot_fit_batch`).

    Calling it mirrors ``fit_batch`` (same result type, same padding
    contract) but runs the stored ``jax.stages.Compiled`` executable
    directly — *no* tracing, *no* compile, *no* jit-cache lookup on the
    call path. This matters because ``jit_fn.lower().compile()`` does NOT
    populate the jit dispatch cache (verified empirically: the first normal
    ``fit_batch`` call after an AOT compile still pays the full ~100ms
    trace+compile); holding and invoking the Compiled object is the only
    way AOT pre-warming actually removes the cold-start cost."""

    batch: int
    p: int
    n: int
    padded: bool  # compiled with the n_valid/mask seams (the serve path)
    cfg: ParaLiNGAMConfig
    backend: str  # concrete score backend the executable was compiled with
    compiled: object  # jax.stages.Compiled
    compile_seconds: float  # what the pre-warm saved the first request

    def __call__(self, xs, n_valid=None, mask=None) -> BatchFitResult:
        cfg = self.cfg
        _note_backend(cfg, self.backend)
        xs = jnp.asarray(xs, cfg.dtype)
        if xs.shape != (self.batch, self.p, self.n):
            raise ValueError(
                f"CompiledFitBatch is specialized to "
                f"{(self.batch, self.p, self.n)}, got {xs.shape}")
        g0 = jnp.asarray(cfg.gamma0, cfg.dtype)
        gg = jnp.asarray(cfg.gamma_growth, cfg.dtype)
        if self.padded:
            nv = (jnp.full((self.batch,), self.n, jnp.int32)
                  if n_valid is None else jnp.asarray(n_valid, jnp.int32))
            if nv.ndim == 0:
                nv = jnp.broadcast_to(nv, (self.batch,))
            mk = (jnp.ones((self.batch, self.p), bool)
                  if mask is None else jnp.asarray(mask, bool))
            out = self.compiled(xs, g0, gg, nv, mk)
        else:
            if n_valid is not None or mask is not None:
                raise ValueError(
                    "this executable was compiled for exact (unpadded) "
                    "batches; aot_fit_batch(padded=True) for the seams")
            out = self.compiled(xs, g0, gg, None, None)
        order, comps, rounds, conv, b, omega = out
        return BatchFitResult(orders=order, comparisons=comps, rounds=rounds,
                              converged=conv, b=b, noise_var=omega)


def aot_fit_batch(batch: int, p: int, n: int,
                  config: ParaLiNGAMConfig | None = None, *,
                  padded: bool = True, rules=None,
                  prune_below: float = 0.0) -> CompiledFitBatch:
    """Ahead-of-time compile the :func:`fit_batch` pipeline for one
    ``(batch, p, n)`` bucket shape: ``jax.jit(...).lower(...).compile()``
    against abstract ``ShapeDtypeStruct`` inputs — no example data, no
    device execution, just trace + XLA compile.

    The serving engines call this at startup over the configured pow-2
    bucket grid (``AsyncLingamEngine(prewarm=True)``) so the first request
    landing on a fresh bucket no longer eats the compile — which otherwise
    shows up as a latency spike that can trip deadline shedding and, under
    a circuit breaker, look exactly like a sick bucket. ``padded`` selects
    the ``n_valid``/mask variant (what bucketed serving dispatches);
    ``padded=False`` matches the exact-shape fast path."""
    cfg = config or ParaLiNGAMConfig()
    if resolve_order_backend(cfg) == "ring":
        raise ConfigError("aot_fit_batch compiles the vmapped scan pipeline; "
                          "the ring driver has no batched form")
    from repro.kernels import ops as kops

    backend = kops.select_backend(cfg, batched=True)
    fn = _pipeline_fn(
        True, rules,
        adjacency=True,
        threshold=cfg.threshold,
        block_j=cfg.block_j, backend=backend,
        min_bucket=cfg.min_bucket, chunk=cfg.chunk, max_rounds=cfg.max_rounds,
        prune_below=prune_below,
    )
    sds = jax.ShapeDtypeStruct
    x_s = sds((batch, p, n), cfg.dtype)
    g_s = sds((), cfg.dtype)
    nv_s = sds((batch,), jnp.int32) if padded else None
    mk_s = sds((batch, p), jnp.bool_) if padded else None
    t0 = time.perf_counter()
    compiled = fn.lower(x_s, g_s, g_s, nv_s, mk_s).compile()
    dt = time.perf_counter() - t0
    return CompiledFitBatch(batch=batch, p=p, n=n, padded=padded, cfg=cfg,
                            backend=backend, compiled=compiled,
                            compile_seconds=dt)


def causal_order_batch(xs, config: ParaLiNGAMConfig | None = None, *,
                       n_valid=None, mask=None, rules=None) -> BatchFitResult:
    """Batched causal order only (phase 1): :func:`fit_batch` without the
    adjacency epilogue. Same padding/sharding contracts (and like it, no
    ring form — ``order_backend="ring"`` raises rather than being silently
    ignored)."""
    cfg = config or ParaLiNGAMConfig()
    xs, nv, mk = _coerce_batch(xs, cfg, n_valid, mask, "causal_order_batch")
    order, comps, rounds, conv = _run_pipeline(
        xs, cfg, adjacency=False, batched=True, n_valid=nv, mask0=mk,
        rules=rules,
    )
    return BatchFitResult(orders=order, comparisons=comps, rounds=rounds,
                          converged=conv)
