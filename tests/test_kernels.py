"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.covariance import cov_matrix, normalize
from repro.kernels import ops, ref
from repro.kernels.pairwise_score import pairwise_score


@pytest.mark.parametrize(
    "p,n", [(8, 512), (16, 1024), (20, 777), (33, 1500), (64, 2048), (7, 130)]
)
def test_pairwise_score_matches_ref(p, n):
    rng = np.random.default_rng(p * 1000 + n)
    x = rng.standard_normal((p, n))
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    hr_k = ops.residual_entropy_matrix(xn, c)
    hr_r = ref.residual_entropy_matrix_ref(xn, c)
    m = ~np.eye(p, dtype=bool)
    np.testing.assert_allclose(
        np.asarray(hr_k)[m], np.asarray(hr_r)[m], rtol=1e-5, atol=1e-5
    )


# Block shapes the TPU kernel compiler takes: block_i a multiple of 8,
# block_n of 128, block_j a multiple of 128 or the whole 8-rounded column
# count (24 here; 128 is clamped to it).
@pytest.mark.parametrize("block", [(8, 128, 128), (8, 24, 256), (16, 128, 512)])
def test_pairwise_score_block_shapes(block):
    bi, bj, bn = block
    rng = np.random.default_rng(0)
    x = rng.standard_normal((24, 640))
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    hr_k = pairwise_score(xn, c, block_i=bi, block_j=bj, block_n=bn, interpret=True)
    hr_r = ref.residual_entropy_matrix_ref(xn, c)
    m = ~np.eye(24, dtype=bool)
    np.testing.assert_allclose(
        np.asarray(hr_k)[m], np.asarray(hr_r)[m], rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("p,n", [(8, 512), (21, 1000), (64, 4096)])
def test_covupdate_matches_ref(p, n):
    rng = np.random.default_rng(p)
    x = rng.standard_normal((p, n))
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    b = np.asarray(c[:, 0]).copy()
    b[0] = 0.0
    b = jnp.asarray(b)
    xd_k = ops.update_data(xn, xn[0], b)
    cd_k = ops.update_cov(c, b)
    xd_r, cd_r = ref.update_data_cov_ref(xn, c, b, xn[0])
    np.testing.assert_allclose(np.asarray(xd_k), np.asarray(xd_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cd_k), np.asarray(cd_r), rtol=1e-5, atol=1e-6)


def test_pairwise_padding_exact():
    """n not divisible by block_n: zero-padding must not bias the moments."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((9, 700))  # 700 % 512 != 0
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    hr_k = pairwise_score(xn, c, block_n=512, interpret=True)
    hr_r = ref.residual_entropy_matrix_ref(xn, c)
    m = ~np.eye(9, dtype=bool)
    np.testing.assert_allclose(np.asarray(hr_k)[m], np.asarray(hr_r)[m], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,p_dim,n", [(2, 16, 16, 16), (4, 32, 64, 128), (1, 8, 32, 64)])
def test_ssd_decode_kernel_matches_ref(b, h, p_dim, n):
    from repro.kernels.ssd_decode import ssd_decode, ssd_decode_ref

    rng = np.random.default_rng(b * 100 + h)
    state = jnp.asarray(rng.standard_normal((b, h, p_dim, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, h, p_dim)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, h)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((h,)), jnp.float32)

    y_k, s_k = ssd_decode(state, x, dt, bb, cc, a, d, block_h=min(8, h), interpret=True)
    y_r, s_r = ssd_decode_ref(state, x, dt, bb, cc, a, d)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_h", [8, 2])  # default and non-default tiling
def test_ssd_decode_kernel_matches_model_decode(block_h):
    """The kernel's math == the model's mamba2_decode state update, for the
    default head-block and a non-default one (grid (B, H/BH) changes)."""
    from repro.kernels.ssd_decode import ssd_decode
    from repro import configs
    from repro.models import ssm as ssm_mod
    from repro.dist.sharding import NO_SHARDING

    cfg = configs.smoke("mamba2-370m")
    params, _ = ssm_mod.init_mamba2(jax.random.PRNGKey(0), cfg, jnp.float32)
    b = 2
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model), jnp.float32)
    state0 = (
        jnp.zeros((b, cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
        jax.random.normal(jax.random.PRNGKey(2),
                          (b, cfg.ssm_conv - 1,
                           cfg.d_inner + 2 * cfg.ssm_state), jnp.float32),
    )
    out_ref, (state_ref_new, _) = ssm_mod.mamba2_decode(params, x, cfg, NO_SHARDING, state0)

    # reproduce the inner state update via the kernel
    z, conv_in, dt = ssm_mod._projections(params, x, cfg)
    window = jnp.concatenate([state0[1], conv_in], axis=1)
    conv_out = jax.nn.silu(
        jnp.sum(window * params["conv_w"][None], axis=1) + params["conv_b"][None]
    )
    di, n = cfg.d_inner, cfg.ssm_state
    xin = conv_out[..., :di].reshape(b, cfg.n_ssm_heads, cfg.ssm_headdim)
    b_t = conv_out[..., di : di + n]
    c_t = conv_out[..., di + n :]
    a = -jnp.exp(params["a_log"])
    y_k, s_k = ssd_decode(state0[0], xin, dt[:, 0], b_t, c_t, a,
                          params["d_skip"], block_h=block_h, interpret=True)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(state_ref_new),
                               rtol=1e-5, atol=1e-5)
