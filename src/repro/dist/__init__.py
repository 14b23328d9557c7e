"""Distributed layer: sharding rules and the ppermute ring (find-root and
the full ring-driven causal order). Meshes come from ``jax.make_mesh``; the
ring driver picks up the mesh made active by ``jax.set_mesh``.
"""

from repro.dist.sharding import NO_SHARDING, ShardingRules, make_rules
