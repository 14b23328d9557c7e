"""Jit'd public wrappers for the Pallas kernels + the score-backend resolver.

On CPU (this container) kernels run in ``interpret=True`` mode for
correctness validation; on TPU they compile natively. The dry-run lowering
path uses the pure-jnp oracles (``repro.core.pairwise``) so the compiled HLO
reflects the XLA-native formulation on the 512-device mesh — kernel
micro-performance is reasoned about separately in EXPERIMENTS.md.

Sample-sharded moments seam (IMPLEMENTED): the ring paths (``dist/ring.py`` /
``dist/ring_order.py``) shard the samples axis over ``model`` and pmean the
two Hyvarinen moments across shards *before* the nonlinear entropy epilogue.
``pairwise_moments`` below returns exactly the raw (m1, m2) moment *sums* —
not the finished entropy — so the cross-device combine stays a plain moment
mean: ``residual_entropy_block(backend="pallas")`` runs this kernel per
shard and hands the sums to ``pairwise.finalize_moments(psum_axis=...)``,
which owns the denominator and the pmean. Orders produced by the kernel-fed
ring are bit-identical to the serial oracle (tests/test_kernel_moments.py).

Batched-fit seam (IMPLEMENTED): ``paralingam.fit_batch`` vmaps the whole
pipeline over a leading dataset axis and threads ``n_valid`` (true sample
count of shape-padded datasets) through every moment denominator. The
kernels accumulate raw moment *sums* and take the valid count as a
scalar-prefetch operand applied only at the finalize divide — zero-padded
sample columns contribute ``log_cosh(0) = 0`` and ``0 * exp(0) = 0`` to the
sums, so the padded-column contract survives exactly. The batch axis is a
leading grid axis: ``fused_score_batch`` spells it as grid (B, T, nk) with a
leading BlockSpec index and a per-dataset prefetched denominator read at
``program_id(0)``; ``jax.vmap`` of ``score_vector`` lowers to the same
growth and is what ``fit_batch``'s vmapped pipeline uses. The former silent
``use_kernel`` drop on ``n_valid`` paths is gone — ``select_backend`` either
honors the request or raises ``BackendUnavailable``.
"""

from __future__ import annotations

import jax

from repro.kernels import covupdate as _covupdate
from repro.kernels import fused_score as _fused
from repro.kernels import pairwise_score as _pairwise

#: The score-backend enum. ``xla``/``xla_fused`` are the pure-jnp
#: formulations (square HR sweep / fused triangular sweep); ``pallas``/
#: ``pallas_fused`` are the kernel routes (square moments kernel / fused
#: triangular kernel); ``auto`` resolves per call site via
#: ``select_backend``.
SCORE_BACKENDS = ("xla", "xla_fused", "pallas", "pallas_fused", "auto")

#: Backends that dispatch a Pallas kernel.
KERNEL_BACKENDS = ("pallas", "pallas_fused")


class BackendUnavailable(ValueError):
    """A requested score backend cannot serve the requested call shape.

    Raised at trace time by ``select_backend`` instead of silently degrading
    — the pre-redesign behaviour of dropping ``use_kernel`` whenever
    ``n_valid`` was set is exactly the bug class this type exists to kill."""


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def select_backend(cfg, n_valid=None, batched: bool = False) -> str:
    """Resolve a ``score_backend`` request to a concrete backend, once.

    ``cfg`` is either the backend name itself or anything with a
    ``score_backend`` attribute (duck-typed so this layer never imports
    ``core.paralingam``). ``n_valid``/``batched`` describe the call site;
    since the moments redesign both seams are served by every backend, so
    they no longer *restrict* the choice — they are kept in the signature
    because they parameterize the ``auto`` policy (and so future backends
    with narrower contracts have the information to refuse).

    Policy for ``auto``: the fused kernel on TPU (the whole point of the
    kernel family), the square jnp oracle elsewhere — interpret-mode Pallas
    is a correctness harness, not a fast path, and on the oracle platform
    ``auto`` stays bit-identical to the historical default rather than
    silently changing f32 summation order. Explicit requests are always
    honored: asking for ``pallas*`` off-TPU runs interpret mode (the parity
    suites rely on it); asking for ``xla_fused`` anywhere runs the fused
    jnp formulation.

    Raises ``BackendUnavailable`` for names outside ``SCORE_BACKENDS``."""
    backend = cfg if isinstance(cfg, str) else getattr(cfg, "score_backend", "auto")
    if backend not in SCORE_BACKENDS:
        raise BackendUnavailable(
            f"score_backend={backend!r} is not one of {SCORE_BACKENDS}"
        )
    if backend != "auto":
        return backend
    del n_valid, batched  # every concrete backend serves both seams
    return "pallas_fused" if _on_tpu() else "xla"


def pairwise_moments(xi, xj, c, *, block_i: int = 8, block_j: int = 128,
                     block_n: int = 512):
    """Raw Hyvarinen moment sums of the (i, j) residual streams via the
    square moments kernel — ``(m1_sum, m2_sum)``, each (pi, pj), no ``1/n``,
    no entropy. This is the kernel half of the moments contract: finalize
    with ``pairwise.finalize_moments``, which owns the ``n_valid``
    denominator and the ``psum_axis`` cross-shard mean. jnp oracle:
    ``pairwise.stream_moments``."""
    return _pairwise.pairwise_moments(
        xi, xj, c,
        block_i=block_i, block_j=block_j, block_n=block_n,
        interpret=not _on_tpu(),
    )


def residual_entropy_matrix(xn, c, *, block_i: int = 8, block_j: int = 128,
                            block_n: int = 512, n_valid=None):
    """HR matrix via the moments kernel + jnp entropy epilogue."""
    return _pairwise.pairwise_score(
        xn, c,
        block_i=block_i, block_j=block_j, block_n=block_n,
        interpret=not _on_tpu(), n_valid=n_valid,
    )


def score_vector(xn, c, mask, *, block: int = 8, block_n: int = 512,
                 n_valid=None):
    """Messaging-folded (p,) score vector via the fused triangular kernel —
    each unordered block pair loaded once, raw-sum accumulators finalized
    in-kernel against the scalar-prefetched valid count, stat + credit
    applied in VMEM, no (p, p) HR round-trip. Under ``jax.vmap`` the grid
    grows a leading batch axis (``fit_batch``'s route). jnp oracle:
    ``repro.core.pairwise.fused_scores``."""
    return _fused.fused_score_vector(
        xn, c, mask, block=block, block_n=block_n,
        interpret=not _on_tpu(), n_valid=n_valid,
    )


def score_batch(xb, cb, maskb, *, block: int = 8, block_n: int = 512,
                n_valid=None):
    """Batched (B, p) score sweep on the explicit (B, T, nk) grid with
    per-dataset prefetched denominators (``fused_score_batch``)."""
    return _fused.fused_score_batch(
        xb, cb, maskb, block=block, block_n=block_n,
        interpret=not _on_tpu(), n_valid=n_valid,
    )


def pair_moments(xn, c_vals, xj, n_valid=None, psum_axis: str | None = None):
    """Both-direction residual entropies for the threshold scheduler's
    gathered comparison chunks (``(m, B)`` each; see
    ``repro.core.pairwise.pair_moments``).

    The chunk layout is a *gather* over pending targets, not a dense tile, so
    there is no Pallas formulation: random-access rows defeat the BlockSpec
    tiling the square/fused kernels rely on. All backends therefore share the
    XLA-native implementation, and the threshold scheduler calls it directly
    (``repro.core.paralingam._find_root_threshold_impl``). This wrapper is
    the kernel-layer name reserved for a future TPU dynamic-gather kernel —
    it is NOT yet on the scheduler's call path; wiring it in (behind a new
    ``SCORE_BACKENDS`` entry) is part of adding that kernel."""
    from repro.core.pairwise import pair_moments as _pair_moments

    return _pair_moments(xn, c_vals, xj, n_valid=n_valid, psum_axis=psum_axis)


def update_data(x, x_root, b, *, block_i: int = 8, block_n: int = 512):
    """Fused Algorithm 7 rank-1 data refresh via the covupdate kernel."""
    return _covupdate.update_data(
        x, x_root, b, block_i=block_i, block_n=block_n,
        interpret=not _on_tpu(),
    )


def update_cov(c, b, *, block_i: int = 8, block_j: int = 128):
    """Fused Algorithm 8 covariance refresh via the covupdate kernel."""
    return _covupdate.update_cov(
        c, b, block_i=block_i, block_j=block_j,
        interpret=not _on_tpu(),
    )
