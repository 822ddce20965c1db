"""Every Pallas kernel of the main path, compiled by the TPU compiler for a
described (not attached) v5e chip at the widths ``chip_smoke.py`` drives.

Interpret mode accepts slices and block shapes that Mosaic refuses (a
dynamic slice on the lane axis, a ``(1, 1)`` output block); these compiles
catch such refusals with no chip. The kernels are called directly with
``interpret=False``: ``kernels.ops`` still sees the CPU backend here.

The topology is described inside a module fixture only, so that under
several pytest-xdist workers just the worker running this file loads the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.blocked_cd import blocked_cd_pallas
from repro.kernels.gram_cd import gram_cd_pallas
from repro.kernels.logistic_stats import logistic_stats_pallas
from repro.kernels.sparse_slab import slab_gram_pallas, slab_spmv_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e device, with the persistent compilation
    cache off: entries written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("f", [128, 512])
def test_gram_cd_compiles_for_v5e(one_chip, f):
    vec = _sds((f,), jnp.float32, one_chip)
    scal = _sds((), jnp.float32, one_chip)
    compiled = gram_cd_pallas.lower(
        _sds((f, f), jnp.float32, one_chip), vec, vec, vec, scal, scal,
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("f", [128, 512])
def test_blocked_cd_compiles_for_v5e(one_chip, f):
    vec = _sds((f,), jnp.float32, one_chip)
    scal = _sds((), jnp.float32, one_chip)
    compiled = blocked_cd_pallas.lower(
        _sds((f, f), jnp.float32, one_chip), vec, vec, vec, scal, scal,
        block=16, interpret=False).compile()
    _assert_kernel(compiled)


# K classes of the sparse smoke phase (chip_smoke.SPARSE_TWIN, seed 1 of
# --seed 0). The design's K max is 57 on one chip (n_loc 160,000) and 34
# per data shard on the 2x2 mesh (n_loc 80,000). A restricted solve trims
# K to k_class(K of its working set, K max): a power of two from 8, capped
# at K max, so 8, 16, 32, 57 on one chip and 8, 16, 32, 34 on the mesh.
# Whole-design margins run at K max over all of the shard's features.
@pytest.mark.parametrize("k", [8, 16, 32, 34, 57])
def test_slab_gram_compiles_for_v5e(one_chip, k):
    t = 128
    f32 = _sds((t, k), jnp.float32, one_chip)
    compiled = slab_gram_pallas.lower(
        _sds((t, k), jnp.int32, one_chip), f32, f32, f32,
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("t,k,n_loc", [
    (128, 8, 160_000),        # restricted sparse solve, one chip
    (128, 16, 160_000),
    (128, 32, 160_000),
    (128, 57, 160_000),
    (2048, 57, 160_000),      # whole-design margins, one chip
    (128, 8, 80_000),         # restricted sparse solve, 2x2 mesh shard
    (128, 16, 80_000),
    (128, 32, 80_000),
    (128, 34, 80_000),
    (1024, 34, 80_000),       # whole-design margins, 2x2 mesh shard
    (2000, 8, 128),           # serve: the (p, K) request slab it runs
    (2000, 8, 64),            # serve: other batch capacity classes
    (2000, 8, 256),
    (2000, 16, 512),
])
def test_slab_spmv_compiles_for_v5e(one_chip, t, k, n_loc):
    compiled = slab_spmv_pallas.lower(
        _sds((t, k), jnp.int32, one_chip), _sds((t, k), jnp.float32, one_chip),
        n_loc=n_loc, interpret=False).compile()
    _assert_kernel(compiled)


def test_logistic_stats_compiles_for_v5e(one_chip):
    n = 320_000                       # epsilon's train split
    vec = _sds((n,), jnp.float32, one_chip)
    compiled = logistic_stats_pallas.lower(vec, vec,
                                           interpret=False).compile()
    _assert_kernel(compiled)
