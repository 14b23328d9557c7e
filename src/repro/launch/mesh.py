"""Production mesh definition.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. The dry-run entrypoint (dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; ordinary runs see the real device count.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_ring_mesh(pods: int = 1, ring: int = 1, model: int = 1,
                   devices=None):
    """3-axis ``("pod", "ring", "model")`` mesh for the two-level messaging
    ring (``dist.ring_order``): P pods of R intra-pod shards, samples over
    ``model``. ``pods=1`` is the flat ring with a degenerate pod axis —
    ``dist.sharding.make_rules`` and ``dist.ring.ring_find_root_jit`` both
    consume the mesh without flattening the pod level away. ``devices``
    (default: all of them) must number ``pods * ring * model``."""
    return jax.make_mesh(
        (pods, ring, model), ("pod", "ring", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3, devices=devices,
    )
