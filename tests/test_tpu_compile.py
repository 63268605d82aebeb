"""The live TPU kernels compile for a v5e that is described, not attached.

The TPU compiler refuses what interpret mode cannot see (tiling, VMEM
limits, unsupported precisions), so the crossbar kernels behind the CM
``pallas`` plane are compiled here with ``interpret=False`` at the shapes
``PallasPlane`` produces.  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

from __future__ import annotations

import functools
import re

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


def _fusion_ops(hlo: str):
    """(name, kind, output shape, opcodes of its fused computation) for
    every fusion in an optimized HLO text."""
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"\n(%[\w.\-]+) \(.*?\) -> .*? \{\n(.*?)\n\}", hlo, re.S)}
    for m in re.finditer(r"(%[\w.\-]+) = (.*?) fusion\(.*?kind=(k\w+), "
                         r"calls=(%[\w.\-]+)", hlo):
        ops = {o.group(1) for line in bodies[m.group(4)].splitlines()
               for o in [re.search(r"(?:^|\s)([a-z][a-z0-9\-]*)\(",
                                   line.split(" = ", 1)[-1])] if o}
        yield m.group(1), m.group(3), m.group(2), ops


def test_qwen2_7b_decode_updates_donated_cache_in_place(one_chip,
                                                        monkeypatch):
    """Two qwen2-7b layers at the serving cell's 32 slots x 4096 positions,
    jitted with the cache donated as ``ContinuousBatcher`` does: the output
    cache is the input's buffers, and nothing copies a layer's K/V out of
    it.  The scan over periods runs one ``decode_attn`` kernel a layer,
    which reads the stacked cache in place, so no (B, S, Hkv, D) buffer is
    left in the program (the jnp path sliced one per layer per tensor, and
    copied it again for its einsum), and the temporaries stay far under one
    such slice."""
    from repro.configs.base import depth_cut
    from repro.kernels import decode_attn
    from repro.models import build_model

    # the backend here is the CPU: compile the kernel, not its interpreter
    monkeypatch.setattr(decode_attn, "resolve_interpret",
                        lambda interpret: False)
    model = build_model(depth_cut("qwen2-7b", 2))

    def placed(tree):
        return jax.tree.map(lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    params = placed(jax.eval_shape(model.init, jax.random.key(0)))
    cache = placed(jax.eval_shape(lambda: model.init_cache(32, 4096)))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, _sds((32,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    kv_slice = 32 * 4096 * 4 * 128 * 2          # one layer's K, bf16
    assert mem.temp_size_in_bytes < kv_slice // 64

    hlo = compiled.as_text()
    assert len(re.findall(r"%decode_attn(\.\d+)? = .*custom-call\(", hlo)) == 1
    assert len(re.findall(r" while\(", hlo)) == 1     # the scan over layers
    assert not re.search(r"\[32,4096,4,128\]", hlo)   # (B, S, Hkv, D)
    moves = {"parameter", "constant", "dynamic-slice", "copy", "bitcast"}
    kv = "32,4096,4,128]"
    for name, kind, out, ops in _fusion_ops(hlo):
        if kv in out and kind == "kLoop":
            # no layer's K/V is stacked into a new cache or blended
            assert "[2," + kv not in out, name
            assert ops <= moves, (name, ops - moves)


# grouped matmul rows at the qwen3-moe share's decode (32 slots x top-8) and
# its 2048-token prefill; (k, n) of the gate/up and the down projections
@pytest.mark.parametrize("rows", [256, 16384])
@pytest.mark.parametrize("k,n", [(4096, 1536), (1536, 4096)])
def test_moe_gmm_compiles_to_mosaic(one_chip, rows, k, n):
    from repro.kernels.gmm import gmm

    fn = jax.jit(functools.partial(gmm, interpret=False))
    hlo = fn.lower(_sds((rows, k), jnp.bfloat16, one_chip),
                   _sds((16, k, n), jnp.bfloat16, one_chip),
                   _sds((16,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the name a trace reader matches the kernel's operations by
    assert re.search(r"%moe_gmm(\.\d+)? = .*custom-call\(", hlo)


def test_qwen3_moe_share_decode_step_compiles_one_layer(one_chip,
                                                        monkeypatch):
    """One layer of the qwen3-moe chip share (16 of 128 experts) at the
    serving cell's 32 slots x 4096, cache donated: the attention is one
    ``decode_attn`` kernel, the expert layer three ``moe_gmm`` kernels, and
    the step fits the chip."""
    from repro.configs.base import depth_cut
    from repro.kernels import decode_attn, gmm
    from repro.models import build_model

    # the backend here is the CPU: compile the kernels, not their
    # interpreter
    for mod in (gmm, decode_attn):
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)

    cfg = depth_cut("qwen3-moe-235b-a22b-d8-e16", 1)
    assert cfg.moe.held == (0, 16)
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    params = placed(jax.eval_shape(model.init, jax.random.key(0)))
    cache = placed(jax.eval_shape(lambda: model.init_cache(32, 4096)))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, _sds((32,), jnp.int32, one_chip),
        _sds((32,), jnp.bool_, one_chip)).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%moe_gmm(\.\d+)? = .*custom-call\(", hlo)) == 3
    assert len(re.findall(r"%decode_attn(\.\d+)? = .*custom-call\(", hlo)) == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 * 2**30


def _decoder_heads():
    """One registered decoder for each (Hq, Hkv, D) the registry holds."""
    from repro.configs.base import all_archs, get_arch

    seen = {}
    for name in all_archs():
        cfg = get_arch(name)
        if cfg.n_heads:
            seen.setdefault((cfg.n_heads, cfg.n_kv_heads, cfg.hd), name)
    return sorted(seen.values())


@pytest.mark.parametrize("name", _decoder_heads())
def test_decode_attention_compiles_for_every_decoder(one_chip, monkeypatch,
                                                     name):
    """One decode attention layer of each registered decoder's heads at 32
    slots x 4096 positions over two periods, cache donated.  Where the TPU
    keeps the cache's (Hkv, D) in rows (``reads_in_place``) the attention
    is one ``decode_attn`` kernel, which fits VMEM and copies no K/V;
    elsewhere it stays on the XLA path, and the kernel alone would have
    relaid the whole cache out first."""
    from repro.configs.base import get_arch
    from repro.kernels import decode_attn as da
    from repro.models import layers as L

    monkeypatch.setattr(da, "resolve_interpret", lambda interpret: False)
    cfg = get_arch(name)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = jnp.dtype(cfg.compute_dtype)
    in_rows = da.reads_in_place(hkv, d, dt)
    params = jax.tree.map(lambda l: _sds(l.shape, l.dtype, one_chip),
                          jax.eval_shape(lambda: L.init_attention(
                              cfg, jax.random.key(0))))
    kv = _sds((2, 32, 4096, hkv, d), dt, one_chip)
    cache_bytes = 2 * 2 * 32 * 4096 * hkv * d * dt.itemsize
    slots = (_sds((32,), jnp.int32, one_chip), _sds((32,), jnp.bool_,
                                                    one_chip))
    compiled = jax.jit(
        lambda p, x, c, n, live: L.attention_decode(cfg, p, x, c, n, 1,
                                                    live),
        donate_argnums=(2,)).lower(
        params, _sds((32, 1, cfg.d_model), dt, one_chip),
        {"k": kv, "v": kv}, *slots).compile()
    hlo = compiled.as_text()
    kernels = len(re.findall(r"%decode_attn(\.\d+)? = .*custom-call\(", hlo))
    assert kernels == int(in_rows)
    if in_rows:
        # the cache updated in place, and neither a period sliced out of it
        # nor the cache relaid
        assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
        assert f"[32,4096,{hkv},{d}]" not in hlo
        assert not re.search(rf"\[2,32,4096,{hkv},{d}\]\S* copy\(", hlo)
    else:
        alone = jax.jit(da.decode_attn).lower(
            _sds((32, hq, d), dt, one_chip), kv, kv,
            _sds((), jnp.int32, one_chip), *slots).compile()
        assert alone.memory_analysis().temp_size_in_bytes >= cache_bytes // 2


def test_qwen3_moe_share_prefill_compiles_with_flash_attention(one_chip,
                                                              monkeypatch):
    """Two layers of the qwen3-moe chip share prefilled at the serving
    cell's largest bucket, 2048: each layer's attention is one
    ``flash_attn`` kernel, and no score tensor (64 heads x 2048 x 2048, or a
    512-row chunk of it) is left in the program."""
    from repro.configs.base import depth_cut
    from repro.kernels import flash_attn, gmm
    from repro.models import build_model

    for mod in (gmm, flash_attn):
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)
    cfg = depth_cut("qwen3-moe-235b-a22b-d8-e16", 2)
    model = build_model(cfg)
    s = 2048

    def prefill(p, toks, n):
        _, cache, load = model.prefill(p, {"tokens": toks}, 4096,
                                       jnp.arange(s)[None] < n)
        return cache, load

    params = jax.tree.map(lambda l: _sds(l.shape, l.dtype, one_chip),
                          jax.eval_shape(model.init, jax.random.key(0)))
    hlo = jax.jit(prefill).lower(
        params, _sds((1, s), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile().as_text()
    assert len(re.findall(r"%flash_attn(\.\d+)? = .*custom-call\(", hlo)) == 2
    assert not re.search(r"\[(1,)?(4,16|64),(512|2048),2048\]", hlo)


def test_qwen3_moe_expert_parallel_decode_compiles(topo, monkeypatch):
    """One qwen3-moe layer at published widths, all 128 experts over a
    (1 data, 4 model) mesh of the described v5e as ``sharding.rules`` lays
    them out: each chip runs its 32 experts' ``moe_gmm`` kernels on its own
    weights, and no expert weight is gathered.  Under a mesh the decode
    attention stays on the XLA path: no ``decode_attn`` kernel."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding

    from repro import sharding as sh
    from repro.configs.base import depth_cut
    from repro.kernels import gmm
    from repro.models import build_model

    monkeypatch.setattr(gmm, "resolve_interpret", lambda interpret: False)
    cfg = depth_cut("qwen3-moe-235b-a22b", 1)
    model = build_model(cfg)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def placed(tree, specs):
        return jax.tree.map(
            lambda l, s: _sds(l.shape, l.dtype, NamedSharding(mesh, s)),
            tree, specs)

    psds = jax.eval_shape(model.init, jax.random.key(0))
    params = placed(psds, sh.param_specs(cfg, psds, mesh))
    csds = jax.eval_shape(lambda: model.init_cache(32, 256))
    cache = placed(csds, sh.cache_specs(cfg, csds, mesh))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    with jax.set_mesh(mesh):
        hlo = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, cache, _sds((32,), jnp.int32, rep),
            _sds((32,), jnp.bool_, rep)).compile().as_text()
    assert len(re.findall(r"%moe_gmm(\.\d+)? = .*custom-call\(", hlo)) == 3
    assert not re.search(r"%decode_attn(\.\d+)? = .*custom-call\(", hlo)
    gathered = re.findall(r"= \w+\[([\d,]*)\]\S* all-gather\(", hlo)
    assert not [g for g in gathered if "1536" in g.split(",")], gathered
