"""The scan's top stage sized to the data: ``p`` rounded up to the bucket
floor instead of ``next_pow2(p)`` (``repro.utils.schedule.make_schedule``).

Dead rows at the top of a stage buffer are copies of row 0 with mask 0, so
a smaller buffer must leave the live scores bit-identical; fits on a
non-power-of-two p must keep the ``xla`` oracle's orders; the ``xla``
backend's j-blocks must stay ``block_j`` wide on such a buffer; and
``score_pair_slots`` counts the dense plan's buffer pairs once per data
set."""

import math
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import direct_lingam, sem
from repro.core.covariance import cov_matrix, normalize
from repro.core.pairwise import residual_entropy_matrix
from repro.core.paralingam import (
    ParaLiNGAMConfig,
    causal_order,
    fit,
    fit_batch,
    lower_fit,
)
from repro.kernels.fused_score import fused_score_vector
from repro.kernels.ref import residual_entropy_matrix_ref
from repro.utils import tracing
from repro.utils.schedule import make_schedule


def _gen(p, n, seed):
    return sem.generate(sem.SemSpec(p=p, n=n, seed=seed))["x"]


def _padded(xn, c, mask, m):
    """The scan's buffer of ``m`` rows: live rows first, the rest copies of
    row 0, masked (``jnp.nonzero(mask, size=m, fill_value=0)``)."""
    p = xn.shape[0]
    sel = jnp.concatenate([jnp.arange(p), jnp.zeros((m - p,), jnp.int32)])
    mk = jnp.concatenate([mask, jnp.zeros((m - p,), bool)])
    return xn[sel], c[sel][:, sel], mk


@pytest.mark.parametrize("dead", [(), (5, 61)])
def test_fused_scores_exact_under_smaller_padding(dead):
    """70 rows (two of them masked in one case) in buffers of next_pow2 =
    128, round_up(70, 32) = 96 and round_up(70, 8) = 72 rows: the fused
    kernel's live scores are bit-identical. Live tiles keep their order in
    the i-major tile map and dead tiles add exactly +0.0."""
    p = 70
    xn = normalize(jnp.asarray(_gen(p, 256, seed=7), jnp.float32))
    c = cov_matrix(xn)
    mask = jnp.ones((p,), bool).at[jnp.asarray(dead, jnp.int32)].set(False)
    scores = {
        m: np.asarray(fused_score_vector(*_padded(xn, c, mask, m),
                                         interpret=True))[:p]
        for m in (128, 96, 72)
    }
    assert np.isfinite(scores[128][np.asarray(mask)]).all()
    for m in (96, 72):
        assert np.array_equal(scores[m], scores[128]), m


P_SMALL, N_SMALL = 37, 256
# min_bucket 8: the top stage is 40 rows, not 64
CFG_SMALL = dict(min_bucket=8)


def test_small_plan_engages_the_sized_top_stage():
    assert make_schedule(P_SMALL, 8).stages[0] == (40, 5)


def test_fit_orders_match_the_oracle_at_non_pow2_p():
    x = _gen(P_SMALL, N_SMALL, seed=3)
    res, b = fit(x, ParaLiNGAMConfig(score_backend="pallas_fused",
                                     **CFG_SMALL))
    ref, b_ref = fit(x, ParaLiNGAMConfig(score_backend="xla", **CFG_SMALL))
    assert res.order == ref.order
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), atol=1e-5)


def test_fit_batch_orders_match_the_oracle_at_non_pow2_p():
    xs = np.stack([_gen(P_SMALL, N_SMALL, seed=20 + i) for i in range(2)])
    res = fit_batch(xs, ParaLiNGAMConfig(score_backend="pallas_fused",
                                         **CFG_SMALL))
    ref = fit_batch(xs, ParaLiNGAMConfig(score_backend="xla", **CFG_SMALL))
    assert np.array_equal(np.asarray(res.orders), np.asarray(ref.orders))


def test_threshold_scan_at_non_pow2_p():
    """The threshold scan's top stage holds whole chunks: 37 rows at
    min_bucket 8 and chunk 16 take 48 rows (not the dense plan's 40, where
    the chunk would shrink to 10), and the order is the host threshold
    driver's and the serial oracle's."""
    assert make_schedule(P_SMALL, 8, chunk=16).stages[0] == (48, 5)
    x = _gen(P_SMALL, 1500, seed=4)
    kw = dict(threshold=True, chunk=16, gamma0=1e-6, **CFG_SMALL)
    scan = causal_order(x, ParaLiNGAMConfig(order_backend="scan", **kw))
    host = causal_order(x, ParaLiNGAMConfig(order_backend="host", **kw))
    assert scan.converged
    assert scan.order == host.order == direct_lingam.causal_order(x)


def test_residual_entropy_matrix_pads_awkward_p():
    """37 rows in j-blocks of 32: the last block is padded with zero
    columns and sliced off. HR equals the one-block result, and the
    reference off the diagonal (where c = 1 makes the residual
    degenerate and no score reads it)."""
    xn = normalize(jnp.asarray(_gen(P_SMALL, N_SMALL, seed=5), jnp.float32))
    c = cov_matrix(xn)
    hr = np.asarray(residual_entropy_matrix(xn, c, block_j=32))
    assert hr.shape == (P_SMALL, P_SMALL)
    assert np.array_equal(
        hr, np.asarray(residual_entropy_matrix(xn, c, block_j=P_SMALL)))
    off = ~np.eye(P_SMALL, dtype=bool)
    np.testing.assert_allclose(
        hr[off], np.asarray(residual_entropy_matrix_ref(xn, c))[off],
        atol=1e-5)


def test_xla_hr_buffer_stays_block_sized():
    """p = 100 at min_bucket 8 has a 104-row top stage, which 32 does not
    divide: the ``xla`` backend's residual buffer stays (104, 32, n), never
    the (104, 104, n) of one block over the whole buffer."""
    n = 256
    assert make_schedule(100, 8).stages[0][0] == 104
    x = _gen(100, n, seed=6)
    text = lower_fit(x, ParaLiNGAMConfig(score_backend="xla",
                                         min_bucket=8)).as_text()
    shapes = set(re.findall(rf"tensor<(\d+)x(\d+)x{n}xf32>", text))
    assert ("104", "32") in shapes
    assert all(int(bj) <= 32 for _, bj in shapes), sorted(shapes)


# e_coli_core's plan at the default floor: ((96, 21), (64, 32), (32, 31))
SLOTS_85 = (21 * math.comb(96, 2) + 32 * math.comb(64, 2)
            + 31 * math.comb(32, 2))


def test_score_pair_slots_counts_one_fit():
    x = _gen(85, 300, seed=1)
    tracing.reset("score_pair_slots")
    res, _ = fit(x)
    slots = tracing.counters("score_pair_slots")["score_pair_slots"]
    assert slots == SLOTS_85 == make_schedule(85, 32).pair_slots
    # the fit's padded share, 1 - comparisons_dense / score_pair_slots
    assert res.comparisons_dense == math.comb(86, 3)
    assert round(1 - res.comparisons_dense / slots, 2) == 0.42


def test_score_pair_slots_counts_each_data_set_of_a_batch():
    xs = np.stack([_gen(85, 300, seed=10 + i) for i in range(3)])
    tracing.reset("score_pair_slots")
    fit_batch(xs)
    assert tracing.counters("score_pair_slots") == {
        "score_pair_slots": 3 * SLOTS_85}


def test_score_pair_slots_skips_threshold_fits():
    """A threshold fit sweeps only its pending pairs, not the plan's
    buffers, so it adds nothing to the dense count."""
    tracing.reset("score_pair_slots")
    fit(_gen(P_SMALL, N_SMALL, seed=2),
        ParaLiNGAMConfig(threshold=True, **CFG_SMALL))
    assert tracing.counters("score_pair_slots") == {"score_pair_slots": 0}
