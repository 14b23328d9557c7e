"""Distributed ParaLiNGAM: the row-block ring find-root AND the ring-driven
full causal order over the devices this host has.

    PYTHONPATH=src python examples/distributed_ring.py

On a CPU-only machine, give it host devices to ring over:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/distributed_ring.py

The mesh is built from ``jax.devices()``: the largest power-of-two count
of them (all of 1, 2, 4 or 8 chips) as ring shards, with a ``model`` axis
of 2 sharding the samples when there are 8 or more.
"""

import time

import jax
import jax.numpy as jnp

from repro.core.covariance import cov_matrix, normalize
from repro.core.paralingam import ParaLiNGAMConfig, causal_order_scan, find_root_dense
from repro.core.sem import SemSpec, generate
from repro.dist.ring import ring_find_root_jit
from repro.dist.ring_order import causal_order_ring
from repro.launch.mesh import make_ring_mesh

n_dev = 1 << (len(jax.devices()).bit_length() - 1)  # largest power of two
model = 2 if n_dev >= 8 else 1
ring = n_dev // model


devices = jax.devices()[:n_dev]
mesh = make_ring_mesh(pods=1, ring=ring, model=model, devices=devices)
print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))

data = generate(SemSpec(p=64, n=4096, seed=1))
xn = normalize(jnp.asarray(data["x"], jnp.float32))
c = cov_matrix(xn)
mask = jnp.ones((64,), bool)

root_1, s_1 = find_root_dense(xn, c, mask, block_j=64)
with jax.set_mesh(mesh):
    fn = ring_find_root_jit(mesh)
    root_r, s_r = fn(xn, c, mask)  # compile
    t0 = time.time()
    for _ in range(5):
        root_r, s_r = fn(xn, c, mask)
    jax.block_until_ready(s_r)
    dt = (time.time() - t0) / 5

print(f"single-device root={int(root_1)}  ring root={int(root_r)}  "
      f"scores match: {bool(jnp.allclose(s_1, s_r, rtol=2e-4))}")
print(f"ring find-root: {dt * 1e3:.1f} ms / iteration on {n_dev} "
      f"{jax.devices()[0].platform} devices")

# --- full causal order through the ring: all p iterations device-resident
# on the ("pod", "ring", "model") mesh — row-block shards over "ring",
# sample shards with psum'd entropy moments over "model".
cfg = ParaLiNGAMConfig(order_backend="ring", min_bucket=8)
res_scan = causal_order_scan(data["x"], ParaLiNGAMConfig(min_bucket=8))
res_ring = causal_order_ring(data["x"], cfg, mesh=mesh)
print(f"ring order == single-shard scan order: {res_ring.order == res_scan.order}")
print(f"first 8 of causal order: {res_ring.order[:8]}")

# --- threshold inside the ring: the comparison-saving state machine (paper
# Algorithms 4-6) runs per shard, with messaging credits and done-masks
# riding the ring packet. Orders match the dense ring; the device-measured
# counters show the saved work.
cfg_thr = ParaLiNGAMConfig(order_backend="ring", threshold=True, min_bucket=8)
res_thr = causal_order_ring(data["x"], cfg_thr, mesh=mesh)
print(f"ring-threshold order == dense ring order: {res_thr.order == res_ring.order}")
print(
    f"ring-threshold comparisons: {res_thr.comparisons} "
    f"(serial DirectLiNGAM: {res_thr.comparisons_serial}; "
    f"saving {100 * res_thr.saving_vs_serial:.1f}%) "
    f"rounds={res_thr.rounds} converged={res_thr.converged}"
)

# --- two-level (pod, ring) topology, where there are 8 or more devices: 2
# pods of ring/2 shards. Row blocks circulate the intra-pod ring every hop;
# cross-pod exchanges happen once per intra-pod revolution, and every
# ppermute for hop k+1 is issued before computing hop k. Orders stay
# identical to the flat ring; the device-measured wire counters show the
# sequential cross-pod rounds dropping below the flat ring's shards/2.
if ring >= 4:
    hier_mesh = make_ring_mesh(pods=2, ring=ring // 2, model=model,
                               devices=devices)
    cfg_hier = ParaLiNGAMConfig(order_backend="ring", min_bucket=8,
                                ring_topology=(2, ring // 2))
    res_hier = causal_order_ring(data["x"], cfg_hier, mesh=hier_mesh)
    w = res_hier.wire
    print(f"2x{ring // 2} hier order == flat ring order: "
          f"{res_hier.order == res_ring.order}")
    print(
        f"2x{ring // 2} wire counters: {w['hops_intra']} intra + "
        f"{w['hops_cross']} cross-pod ppermute rounds, {w['hops_overlapped']} "
        f"overlapped behind compute (overlap_frac={w['overlap_frac']:.2f}); "
        f"sequential cross-pod rounds/iter = "
        f"{w['seq_cross_hops'] // max(len(res_hier.per_iteration), 1)} "
        f"vs flat ring's {ring // 2}"
    )
