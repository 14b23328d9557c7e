"""Multi-device tests (subprocess with 8 host devices): ring find-root ==
single-device dense; sharded train step runs; MoE shard_map; compression."""

import json
import subprocess
import sys
import textwrap

import pytest


def _run(snippet: str) -> str:
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(snippet)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_ring_find_root_matches_dense():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.covariance import normalize, cov_matrix
    from repro.core.paralingam import find_root_dense
    from repro.dist.ring import ring_find_root_jit

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    p, n = 32, 1024
    x = rng.standard_normal((p, n))
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    mask = jnp.ones((p,), bool)
    root_d, s_d = find_root_dense(xn, c, mask, block_j=32)
    with jax.set_mesh(mesh):
        fn = ring_find_root_jit(mesh)
        root_r, s_r = fn(xn, c, mask)
    assert int(root_d) == int(root_r)
    np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_r), rtol=2e-4, atol=1e-5)
    print("RING_OK")
    """)
    assert "RING_OK" in out


def test_ring_driver_reads_set_mesh():
    """``fit(order_backend="ring")`` inside ``with jax.set_mesh(mesh)`` rings
    over that mesh — here 2 of the 8 devices, with samples sharded over its
    ``model`` axis — and over all 8 devices once no mesh is active."""
    out = _run("""
    import jax, numpy as np
    from repro.core import direct_lingam, sem
    from repro.core.paralingam import ParaLiNGAMConfig, fit
    from repro.dist.ring_order import _canonical_mesh

    mesh = jax.make_mesh((1, 2, 2), ("pod", "ring", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3,
                         devices=jax.devices()[:4])
    x = sem.generate(sem.SemSpec(p=16, n=512, seed=3))["x"]
    cfg = ParaLiNGAMConfig(order_backend="ring", min_bucket=8)
    with jax.set_mesh(mesh):
        canon, pods, big_r, sample_axis = _canonical_mesh(None, 512)
        assert list(canon.devices.flat) == list(mesh.devices.flat)
        assert (pods, big_r, sample_axis) == (1, 2, "model"), (pods, big_r)
        res, _ = fit(x, cfg)
    assert res.wire["ring"] == 2, res.wire
    assert _canonical_mesh(None, 512)[2] == 8
    res8, _ = fit(x, cfg)
    assert res8.wire["ring"] == 8, res8.wire
    ref = direct_lingam.causal_order(np.asarray(x, np.float64))
    assert res.order == res8.order == list(ref), (res.order, res8.order, ref)
    print("SET_MESH_OK")
    """)
    assert "SET_MESH_OK" in out


def test_sharded_train_step_runs():
    """A real (allocating) sharded train step on a 4x2 mesh — smoke config."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.models import lm
    from repro.dist.sharding import make_rules
    from repro.train.trainer import make_train_step
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.smoke("llama4-scout-17b-a16e").with_overrides(
        d_model=64, n_experts=4, n_heads=4, n_kv_heads=2)
    rules = make_rules(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    opt = init_opt_state(params)
    step = make_train_step(
        lambda p, b: lm.train_loss(p, b, cfg, rules),
        OptimizerConfig(warmup_steps=0), cast_bf16=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step)
        p2, o2, m = jitted(params, opt, {"tokens": tokens})
        l1 = float(m["loss"])
        p3, o3, m2 = jitted(p2, o2, {"tokens": tokens})
        l2 = float(m2["loss"])
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1
    print("TRAIN_OK", l1, l2)
    """)
    assert "TRAIN_OK" in out


def test_moe_sharded_matches_single_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.models import moe
    from repro.dist.sharding import make_rules, NO_SHARDING

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.smoke("llama4-scout-17b-a16e").with_overrides(
        d_model=64, n_experts=8, top_k=2)
    params, _ = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)
    out_1, aux_1 = moe.moe_ffn(params, x, cfg, NO_SHARDING)
    rules = make_rules(cfg, mesh)
    with jax.set_mesh(mesh):
        out_8, aux_8 = jax.jit(lambda p, x: moe.moe_ffn(p, x, cfg, rules))(params, x)
    np.testing.assert_allclose(np.asarray(out_1), np.asarray(out_8), atol=2e-5)
    assert abs(float(aux_1) - float(aux_8)) < 1e-5
    print("MOE_OK")
    """)
    assert "MOE_OK" in out


def test_compressed_psum_schemes():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.train.compression import compressed_psum_mean

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)

    def run(scheme):
        def body(gl):
            out, _ = compressed_psum_mean({"w": gl}, mesh, ("data",), scheme)
            return out["w"]
        with jax.set_mesh(mesh):
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("data", None),
                out_specs=P("data", None), check_vma=False))(g)

    exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
    for scheme, tol in (("bf16", 2e-2), ("int8", 3e-2)):
        got = run(scheme)
        err = float(jnp.abs(got - exact).max()) / (float(jnp.abs(exact).max()) + 1e-9)
        assert err < tol, (scheme, err)
    print("COMP_OK")
    """)
    assert "COMP_OK" in out
