"""Single-process unit tests for the dist layer: sharding-rule specs, ring
schedule properties, and the degenerate 1-device ring — no subprocess / no
multi-device harness, so these run in the fast CI lane."""

import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.covariance import cov_matrix, normalize
from repro.core.paralingam import find_root_dense
from repro.dist.ring import process_pair, ring_find_root, ring_steps
from repro.dist.ring_order import ring_order_stages
from repro.dist.sharding import NO_SHARDING, ShardingRules, make_rules


def _stub_mesh(**axes):
    """Axis-size stub: ShardingRules only reads ``mesh.shape`` for sizing, so
    spec construction is testable without multi-device hardware."""
    return types.SimpleNamespace(shape=dict(axes))


# ---------------------------------------------------------------------------
# ShardingRules / make_rules
# ---------------------------------------------------------------------------


def test_no_sharding_is_identity():
    x = jnp.ones((2, 8, 16))
    assert NO_SHARDING.act(x, "act") is x
    assert NO_SHARDING.model_axis is None
    assert NO_SHARDING.model_size == 1
    assert NO_SHARDING.batch_shards == 1


def test_rules_axis_sizes():
    rules = ShardingRules(
        mesh=_stub_mesh(pod=2, data=4, model=8),
        batch_axes=("pod", "data"),
        model_axis="model",
    )
    assert rules.model_size == 8
    assert rules.batch_shards == 8


def test_spec_shapes_per_kind():
    rules = ShardingRules(
        mesh=_stub_mesh(data=4, model=2), batch_axes=("data",), model_axis="model"
    )
    assert rules.spec((8, 32, 64), "act") == P(("data",), None, None)
    assert rules.spec((8, 32, 128), "ffn") == P(("data",), None, "model")
    assert rules.spec((8, 32, 512), "logits") == P(("data",), None, "model")
    assert rules.spec((8, 32, 4, 16), "heads") == P(("data",), None, "model", None)
    assert rules.spec((8, 32, 2, 16), "kv_heads") == P(("data",), None, "model", None)


def test_spec_drops_non_dividing_axes():
    rules = ShardingRules(
        mesh=_stub_mesh(data=4, model=2), batch_axes=("data",), model_axis="model"
    )
    # batch 6 % 4 != 0 -> batch axis dropped; heads 3 % 2 != 0 -> model dropped
    assert rules.spec((6, 32, 3, 16), "heads") == P(None, None, None, None)


def test_spec_context_parallel_moves_model_to_seq():
    rules = ShardingRules(
        mesh=_stub_mesh(data=4, model=2), batch_axes=("data",),
        model_axis="model", context_parallel=True, shard_heads=False,
    )
    assert rules.spec((8, 32, 64), "act") == P(("data",), "model", None)
    assert rules.spec((8, 32, 4, 16), "heads") == P(("data",), "model", None, None)


def test_make_rules_single_device_mesh_degenerates():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.smoke("granite-3-2b")
    rules = make_rules(cfg, mesh)
    assert rules.batch_axes == ()
    assert rules.model_axis is None
    x = jnp.ones((2, 8, 16))
    assert rules.act(x, "act").shape == x.shape  # no-op constraint path


def test_make_rules_moe_requires_divisible_experts():
    cfg = configs.smoke("llama4-scout-17b-a16e").with_overrides(n_experts=6)
    rules = make_rules(cfg, _stub_mesh(data=2, model=4))
    assert rules.model_axis is None  # 6 % 4 != 0 -> expert parallelism off
    rules2 = make_rules(
        cfg.with_overrides(n_experts=8), _stub_mesh(data=2, model=4)
    )
    assert rules2.model_axis == "model"


def test_make_rules_batch_axes_override():
    cfg = configs.smoke("granite-3-2b")
    rules = make_rules(cfg, _stub_mesh(data=4, model=2), batch_axes=())
    assert rules.batch_axes == ()
    assert rules.batch_shards == 1


# ---------------------------------------------------------------------------
# the mesh made active by jax.set_mesh reaches the ring driver
# ---------------------------------------------------------------------------


def test_canonical_mesh_reads_set_mesh():
    """``_canonical_mesh(None, ...)`` takes the ``jax.set_mesh`` mesh (its
    ``model`` axis included) while one is active, and all devices once it
    is gone. One device here; the multi-device form is in
    ``test_distributed.py``."""
    from repro.dist.ring_order import _canonical_mesh

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        assert jax.sharding.get_mesh() == mesh
        canon, pods, big_r, _ = _canonical_mesh(None, n=64)
        assert list(canon.devices.flat) == list(mesh.devices.flat)
        assert (pods, big_r) == (1, 1)
    assert jax.sharding.get_mesh().empty
    canon, pods, big_r, sample_axis = _canonical_mesh(None, n=64)
    assert canon.axis_names == ("pod", "ring", "model")
    assert canon.devices.size == len(jax.devices())
    assert sample_axis is None


def test_make_ring_mesh_over_given_devices():
    """``launch.mesh.make_ring_mesh`` spans all devices by default and the
    ones it is given otherwise (a power-of-two subset of a host's chips)."""
    from repro.launch.mesh import make_ring_mesh

    n = len(jax.devices())
    mesh = make_ring_mesh(1, n, 1)
    assert mesh.axis_names == ("pod", "ring", "model")
    assert dict(mesh.shape) == {"pod": 1, "ring": n, "model": 1}
    one = make_ring_mesh(1, 1, 1, devices=jax.devices()[-1:])
    assert list(one.devices.flat) == [jax.devices()[-1]]


# ---------------------------------------------------------------------------
# ring schedule (pure)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r", list(range(1, 13)))
def test_ring_schedule_covers_each_pair_once(r):
    """Every unordered block pair is processed exactly once — the messaging
    invariant: one evaluation, both endpoints credited, no double counting."""
    seen = {}
    for t in range(1, ring_steps(r) + 1):
        for dst in range(r):
            src = (dst - t) % r
            if process_pair(r, t, dst, src):
                seen[frozenset((dst, src))] = seen.get(frozenset((dst, src)), 0) + 1
    want = {frozenset((a, b)) for a in range(r) for b in range(a + 1, r)}
    assert set(seen) == want
    assert all(count == 1 for count in seen.values())


def test_ring_schedule_step_counts():
    # Processed steps are exactly floor(R/2): enough for every block pair to
    # meet once (coverage test above), and the R - R//2 return hops complete
    # a full circle so each accumulator lands back at its owner.
    assert [ring_steps(r) for r in range(1, 9)] == [0, 1, 1, 2, 2, 3, 3, 4]


# ---------------------------------------------------------------------------
# ring-order stage schedule (pure): the compaction sequence
# ---------------------------------------------------------------------------


def _pair_eval_counts(m: int, r: int) -> dict:
    """How often each unordered row pair of an m-row stage buffer is
    evaluated in ONE ring-order iteration: intra-block pairs via the step-0
    self block, inter-block pairs via the ``process_pair`` schedule."""
    m_l = m // r
    counts: dict = {}

    def bump(a, b):
        key = (min(a, b), max(a, b))
        counts[key] = counts.get(key, 0) + 1

    for d in range(r):
        rows = range(d * m_l, (d + 1) * m_l)
        for a in rows:
            for b in rows:
                if a < b:
                    bump(a, b)
    for t in range(1, ring_steps(r) + 1):
        for dst in range(r):
            src = (dst - t) % r
            if process_pair(r, t, dst, src):
                for a in range(dst * m_l, (dst + 1) * m_l):
                    for b in range(src * m_l, (src + 1) * m_l):
                        bump(a, b)
    return counts


@pytest.mark.parametrize("r", [1, 2, 4, 8])
@pytest.mark.parametrize("p,min_bucket", [(8, 8), (17, 8), (33, 16), (64, 8), (100, 32)])
def test_ring_order_schedule_pairs_once_across_compactions(p, min_bucket, r):
    """The antipodal-dedup invariant extended to the full compaction
    sequence: in EVERY iteration of EVERY stage, each unordered row pair of
    the stage buffer (live pairs are a subset) is evaluated exactly once —
    no pair is dropped or double-credited as buckets shrink."""
    stages = ring_order_stages(p, min_bucket, r)
    assert sum(cnt for _, cnt in stages) == p - 1
    sizes = [m for m, _ in stages]
    assert sizes == sorted(sizes, reverse=True)  # buckets only shrink
    live = p
    for m, cnt in stages:
        assert m % r == 0  # whole blocks
        assert m == stages[0][0] or m & (m - 1) == 0  # pow-2 below the top
        counts = _pair_eval_counts(m, r)
        want = {(a, b) for a in range(m) for b in range(a + 1, m)}
        assert set(counts) == want
        assert all(v == 1 for v in counts.values())
        for _ in range(cnt):
            assert live <= m  # buffer always holds every live row
            live -= 1
    assert live == 1  # the final row needs no find-root


def test_ring_order_stages_match_scan_profile_when_ring_degenerate():
    """With r=1 and a pow-2 min_bucket the ring schedule IS the scan
    driver's bucket schedule — same buffers, same iteration counts."""
    from repro.core.paralingam import _scan_stages

    for p, mb in ((8, 8), (17, 8), (64, 32), (100, 32)):
        assert ring_order_stages(p, mb, 1) == _scan_stages(p, mb)


def test_ring_order_stages_reject_non_pow2_ring():
    with pytest.raises(ValueError):
        ring_order_stages(64, 8, 6)


# ---------------------------------------------------------------------------
# ring find-root on the degenerate 1-device mesh
# ---------------------------------------------------------------------------


def _seeded_problem(p, n, seed=0):
    rng = np.random.default_rng(seed)
    xn = normalize(jnp.asarray(rng.standard_normal((p, n)), jnp.float32))
    return xn, cov_matrix(xn)


def test_ring_find_root_degenerate_mesh_matches_dense():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    xn, c = _seeded_problem(16, 512)
    mask = jnp.ones((16,), bool)
    root_d, s_d = find_root_dense(xn, c, mask, block_j=16)
    root_r, s_r = ring_find_root(xn, c, mask, mesh)
    assert int(root_d) == int(root_r)
    np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_r), rtol=2e-4)


def test_ring_find_root_mask_with_dead_rows():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    xn, c = _seeded_problem(16, 512, seed=3)
    mask = jnp.ones((16,), bool).at[jnp.asarray([2, 7, 11])].set(False)
    root_d, s_d = find_root_dense(xn, c, mask, block_j=16)
    root_r, s_r = ring_find_root(xn, c, mask, mesh)
    assert int(root_d) == int(root_r)
    s_d, s_r = np.asarray(s_d), np.asarray(s_r)
    assert np.isinf(s_r[[2, 7, 11]]).all()  # dead rows scored +inf, as dense
    live = np.isfinite(s_d)
    np.testing.assert_allclose(s_d[live], s_r[live], rtol=2e-4)


def test_ring_find_root_non_divisible_p_falls_back():
    # A 4-shard ring cannot split p=15 evenly -> dense fallback, same answer.
    # (The fallback fires before any device communication, so an axis-size
    # stub suffices — no multi-device harness needed to pin this branch.)
    xn, c = _seeded_problem(15, 512, seed=5)
    mask = jnp.ones((15,), bool)
    root_d, s_d = find_root_dense(xn, c, mask, block_j=15)
    root_r, s_r = ring_find_root(xn, c, mask, _stub_mesh(data=4, model=2))
    assert int(root_d) == int(root_r)
    np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_r), rtol=2e-4)
