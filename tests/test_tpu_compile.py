"""The score kernels compile for a TPU v5e at the paper's real widths.

Each test compiles one kernel, natively (``interpret=False``), for a chip
that is described and not attached, and asserts the compiled program holds
the kernel (``tpu_custom_call``). That is what the chip's kernel compiler
(Mosaic) accepts or refuses: block tiling, scalar and vector memory. It runs
nothing, so it says nothing about results or speed. The topology is
described inside a fixture, never at import: only one process at a time may
load the TPU library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fused_score import fused_score_batch, fused_score_vector
from repro.kernels.pairwise_score import pairwise_moments

N = 10_000  # ParaLiNGAM Table 1 sample count


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on the described chip,
    with the persistent compilation cache off: its entries for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("p", [770, 4096, 8192],
                         ids=["ijr904", "iml1515_bucket", "bucket_8192"])
def test_fused_score_vector_compiles(shape, p):
    """IJR904 (p=770), the bucket the scan driver gives IML1515
    (p=2326 -> 4096), whose 130816-tile map must fit scalar memory, and
    the next bucket, where the tile map only fits once ``tri_block`` has
    doubled the row block past 8."""
    from repro.kernels.fused_score import tri_block

    assert (tri_block(p, 8) > 8) == (p > 4096)
    _compiles_kernel(
        lambda x, c, m: fused_score_vector(x, c, m, interpret=False),
        shape((p, N)), shape((p, p)), shape((p,), jnp.bool_))


@pytest.mark.parametrize("padded", [False, True], ids=["exact", "n_valid"])
def test_fused_score_batch_compiles(shape, padded):
    """The explicit (B, T, nk) grid at 8 ECOLI_CORE-shaped data sets, with
    and without per-dataset prefetched denominators."""
    args = [shape((8, 85, N)), shape((8, 85, 85)), shape((8, 85), jnp.bool_)]
    if padded:
        args.append(shape((8,), jnp.int32))
    _compiles_kernel(
        lambda x, c, m, *nv: fused_score_batch(
            x, c, m, interpret=False, n_valid=nv[0] if nv else None),
        *args)


def test_vmapped_score_vector_compiles(shape):
    """``fit_batch``'s route: the single-dataset kernel under ``jax.vmap``."""
    _compiles_kernel(
        jax.vmap(lambda x, c, m, nv: fused_score_vector(
            x, c, m, interpret=False, n_valid=nv)),
        shape((8, 128, N)), shape((8, 128, 128)),
        shape((8, 128), jnp.bool_), shape((8,), jnp.int32))


@pytest.mark.parametrize("pi,pj", [(1024, 1024), (582, 582), (13, 13)])
def test_pairwise_moments_compiles_at_defaults(shape, pi, pj):
    """The square moments kernel at its default blocks: a ring shard's
    block pair at the 4096 stage on four chips, IML1515's uneven quarter,
    and a column count below one lane tile."""
    _compiles_kernel(
        lambda a, b, c: pairwise_moments(a, b, c, interpret=False),
        shape((pi, N)), shape((pj, N)), shape((pi, pj)))
