"""ParaLiNGAM == DirectLiNGAM exactness + threshold/messaging behaviour."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import direct_lingam, sem
from repro.core.covariance import cov_matrix, normalize
from repro.core.pairwise import dense_scores, pair_stat_matrix, residual_entropy_matrix, row_entropies
from repro.core.paralingam import (
    ParaLiNGAMConfig,
    causal_order,
    find_root_dense,
    find_root_threshold,
    fit,
)


def _data(p=8, n=3000, seed=0, density="sparse"):
    return sem.generate(sem.SemSpec(p=p, n=n, density=density, seed=seed))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("density", ["sparse", "dense"])
def test_dense_matches_serial_oracle(seed, density):
    data = _data(p=7, n=2500, seed=seed, density=density)
    serial = direct_lingam.causal_order(data["x"])
    res = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", min_bucket=8))
    assert res.order == serial


@pytest.mark.parametrize("seed", [0, 3])
def test_threshold_matches_serial_oracle(seed):
    data = _data(p=8, n=2500, seed=seed)
    serial = direct_lingam.causal_order(data["x"])
    res = causal_order(
        data["x"],
        ParaLiNGAMConfig(order_backend="host", threshold=True, chunk=4, min_bucket=8),
    )
    assert res.order == serial
    # threshold must never do more work than the messaging-only baseline
    assert res.comparisons <= res.comparisons_dense
    assert res.comparisons_serial == 2 * res.comparisons_dense


def test_threshold_saves_comparisons():
    data = _data(p=16, n=2000, seed=5)
    res = causal_order(
        data["x"],
        ParaLiNGAMConfig(order_backend="host", threshold=True, chunk=4, min_bucket=16, gamma0=1e-6),
    )
    assert 0.0 < res.saving_vs_serial < 1.0
    # messaging alone halves comparisons; threshold should add on top
    assert res.saving_vs_serial > 0.5


def test_recovers_true_causal_order():
    data = _data(p=10, n=6000, seed=7)
    res = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host"))
    assert sem.is_valid_causal_order(res.order, data["b_true"])


def test_fit_recovers_strengths():
    data = _data(p=8, n=8000, seed=11)
    res, b = fit(data["x"])
    assert sem.is_valid_causal_order(res.order, data["b_true"])
    np.testing.assert_allclose(b, data["b_true"], atol=0.12)


def test_stat_matrix_antisymmetric():
    """I(i, j) = -I(j, i) — the messaging identity (paper Section 3.1)."""
    data = _data(p=9, n=2000, seed=2)
    xn = normalize(jnp.asarray(data["x"], jnp.float32))
    c = cov_matrix(xn)
    mask = jnp.ones((9,), bool)
    hx = row_entropies(xn, mask)
    hr = residual_entropy_matrix(xn, c, block_j=9)
    stat = pair_stat_matrix(hx, hr)
    np.testing.assert_allclose(
        np.asarray(stat), -np.asarray(stat).T, atol=1e-5
    )


def test_threshold_same_root_as_dense_per_iteration():
    data = _data(p=12, n=2000, seed=9)
    x = normalize(jnp.asarray(data["x"], jnp.float32))
    c = cov_matrix(x)
    mask = jnp.ones((12,), bool)
    root_d, _ = find_root_dense(x, c, mask, block_j=12)
    root_t, s, comps, rounds, converged = find_root_threshold(
        x, c, mask, 1e-6, 2.0, chunk=4
    )
    assert int(root_d) == int(root_t)
    assert int(comps) <= 12 * 11 // 2
    assert bool(converged)


@pytest.mark.parametrize("seed", [13, 29])
def test_threshold_order_and_savings_p64(seed):
    """The paper's comparison-savings claim at worker scale: on p >= 64 the
    threshold mechanism returns the *identical* causal order to the dense
    path while saving more than half the serial-DirectLiNGAM comparisons
    (messaging alone gives exactly 0.5; the threshold must beat it)."""
    data = sem.generate(sem.SemSpec(p=64, n=1500, density="sparse", seed=seed))
    r_dense = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host"))
    r_thr = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", threshold=True, chunk=16))
    assert r_thr.order == r_dense.order
    # > 0.5 == strictly better than the messaging-only baseline (which saves
    # exactly half of serial: comparisons_serial == 2 * comparisons_dense)
    assert r_thr.saving_vs_serial > 0.5


def test_threshold_truncation_surfaced():
    """max_rounds cutting off Algorithm 6 must not pass silently: the
    converged flag comes back False and causal_order warns + records it."""
    data = _data(p=8, n=1000, seed=1)
    x = normalize(jnp.asarray(data["x"], jnp.float32))
    c = cov_matrix(x)
    mask = jnp.ones((8,), bool)
    *_, conv = find_root_threshold(x, c, mask, 1e-6, 2.0, chunk=2, max_rounds=1)
    assert not bool(conv)

    with pytest.warns(UserWarning, match="max_rounds"):
        res = causal_order(
            data["x"],
            ParaLiNGAMConfig(order_backend="host", threshold=True, chunk=2, max_rounds=1,
                             min_bucket=8),
        )
    assert not res.converged
    assert not res.per_iteration[0]["converged"]

    # ample rounds -> converged, recorded per iteration
    res_ok = causal_order(
        data["x"], ParaLiNGAMConfig(order_backend="host", threshold=True, chunk=2, min_bucket=8)
    )
    assert res_ok.converged
    assert all(it["converged"] for it in res_ok.per_iteration)


def test_bucketing_equivalence():
    data = _data(p=10, n=1500, seed=4)
    r1 = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", bucket=True, min_bucket=4))
    r2 = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", bucket=False))
    assert r1.order == r2.order


def test_kernel_backed_dense_matches():
    data = _data(p=8, n=1024, seed=6)
    r1 = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", score_backend="xla"))
    r2 = causal_order(data["x"], ParaLiNGAMConfig(order_backend="host", score_backend="pallas"))
    assert r1.order == r2.order


def test_fit_is_scale_free_past_f32_square_range():
    """Rows of magnitude ~1e21 square past the f32 range; the row
    statistics scale by powers of two first, so the order is unchanged and
    B is finite and follows the scaling."""
    data = _data(p=8, n=4000, seed=11)
    big = data["x"] * 2.0 ** np.arange(62, 70)[:, None]
    res, b = fit(data["x"])
    res_big, b_big = fit(big)
    assert res_big.order == res.order
    scale = 2.0 ** (np.arange(62, 70)[:, None] - np.arange(62, 70)[None, :])
    np.testing.assert_allclose(np.asarray(b_big), np.asarray(b) * scale,
                               rtol=1e-4, atol=1e-6)
    xn = normalize(jnp.asarray(data["x"], jnp.float32))
    xn_big = normalize(jnp.asarray(big, jnp.float32))
    np.testing.assert_array_equal(np.asarray(xn_big), np.asarray(xn))


def test_standardized_sem_recovers_strengths():
    """``SemSpec.standardize`` keeps every variable at unit variance, and
    ``b_true`` follows it: the fit recovers it like the raw SEM's."""
    data = sem.generate(sem.SemSpec(p=8, n=8000, seed=11, standardize=True))
    np.testing.assert_allclose(data["x"].std(axis=1), 1.0, rtol=1e-12)
    res, b = fit(data["x"])
    assert sem.is_valid_causal_order(res.order, data["b_true"])
    np.testing.assert_allclose(b, data["b_true"], atol=0.12)
