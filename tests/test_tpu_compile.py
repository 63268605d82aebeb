"""The live TPU kernels compile for a v5e that is described, not attached.

The TPU compiler refuses what interpret mode cannot see (tiling, VMEM
limits, unsupported precisions), so the crossbar kernels behind the CM
``pallas`` plane are compiled here with ``interpret=False`` at the shapes
``PallasPlane`` produces.  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mxv import crossbar_mxv_int8_padded, crossbar_mxv_padded


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# crossbar (M, N): the odd lenet conv, one MXU tile, a full 256-wide crossbar
XBARS = [(4, 36), (128, 128), (256, 256)]


@pytest.mark.parametrize("batch", [8, 64])
@pytest.mark.parametrize("m,n", XBARS)
def test_crossbar_mxv_compiles_to_mosaic(one_chip, batch, m, n):
    fn = jax.jit(functools.partial(crossbar_mxv_padded, interpret=False))
    hlo = fn.lower(_sds((batch, n), jnp.float32, one_chip),
                   _sds((m, n), jnp.int8, one_chip),
                   _sds((m,), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch", [8, 64])
@pytest.mark.parametrize("m,n", XBARS)
def test_crossbar_mxv_int8_compiles_to_mosaic(one_chip, batch, m, n):
    fn = jax.jit(functools.partial(crossbar_mxv_int8_padded, interpret=False))
    hlo = fn.lower(_sds((batch, n), jnp.int8, one_chip),
                   _sds((batch,), jnp.float32, one_chip),
                   _sds((m, n), jnp.int8, one_chip),
                   _sds((m,), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_qwen2_7b_decode_step_compiles_one_layer(one_chip):
    """One qwen2-7b layer at published widths, the LM phase's decode step."""
    from repro.configs.base import depth_cut
    from repro.models import build_model

    model = build_model(depth_cut("qwen2-7b", 1))

    def placed(tree):
        return jax.tree.map(lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    params = placed(jax.eval_shape(model.init, jax.random.key(0)))
    cache = placed(jax.eval_shape(lambda: model.init_cache(8, 2048)))
    compiled = jax.jit(model.decode_step).lower(
        params, cache, _sds((8,), jnp.int32, one_chip)).compile()
    # weights + cache of one layer, embed and head: well inside 16 GB
    assert compiled.memory_analysis().argument_size_in_bytes < 4 * 2**30
