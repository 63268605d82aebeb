"""The decode step writes one K/V row per slot into a cache it updates in
place: the written cache equals the one-hot blend it replaced, bit for bit,
and the cache can be donated, by a jitted call and by the batcher."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models import build_model, lm
from repro.models import layers as L
from repro.serve.scheduler import ContinuousBatcher, Request

MAX_LEN = 16
# an empty slot, one mid-sequence, the last position, and idle slots past
# the end, which get no write
LENGTHS = (0, 7, MAX_LEN - 1, MAX_LEN, MAX_LEN + 3)
KV = {"bf16": dict(param_dtype="bfloat16", compute_dtype="bfloat16"),
      "int8": dict(kv_dtype="int8")}


def _cfg(kv: str, static_unroll: bool = False):
    # three periods of one attention layer each
    return dataclasses.replace(smoke_config("qwen2-7b"), n_layers=3,
                               static_unroll=static_unroll, **KV[kv])


def _random_cache(cfg, seed: int):
    """A cache with every position filled, so an unwanted write shows."""
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, len(LENGTHS), MAX_LEN))
    rng = np.random.default_rng(seed)

    def fill(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
        if leaf.shape[-1] == 1:                          # int8 scales
            return jnp.asarray(rng.uniform(0.01, 0.1, leaf.shape),
                               leaf.dtype)
        return jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)

    layers = jax.tree.map(fill, cache["layers"])
    return {"layers": layers, "length": jnp.asarray(LENGTHS, jnp.int32)}


def _blend_decode_step(cfg, params, cache, tokens):
    """The decode step before the in-place write, for a config of attention
    layers with dense MLPs: the scan over periods takes each layer's K/V as
    an input and returns it as an output, and each layer blends its new row
    into all of it by a one-hot over positions, then attends over positions
    <= length."""
    length = cache["length"]
    quant = cfg.kv_dtype == "int8"

    def layer(x, per):
        p, c = per
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = L._project_qkv(cfg, p["attn"], h, h)
        q = L.positional_rotate(cfg, q, length[:, None])
        k = L.positional_rotate(cfg, k, length[:, None])
        oh = jax.nn.one_hot(length, MAX_LEN,
                            dtype=jnp.float32)[..., None, None]
        if quant:
            (k8, ks), (v8, vs) = L.kv_quantize(k), L.kv_quantize(v)
            new = {n: (c[n].astype(jnp.float32) * (1 - oh)
                       + oh * r.astype(jnp.float32)).astype(jnp.int8)
                   for n, r in (("k", k8), ("v", v8))}
            new["k_scale"] = c["k_scale"] * (1 - oh) + oh * ks
            new["v_scale"] = c["v_scale"] * (1 - oh) + oh * vs
            k_eff = new["k"].astype(jnp.float32) * new["k_scale"]
            v_eff = new["v"].astype(jnp.float32) * new["v_scale"]
        else:
            ohc = oh.astype(c["k"].dtype)
            new = {"k": c["k"] * (1 - ohc) + ohc * k,
                   "v": c["v"] * (1 - ohc) + ohc * v}
            k_eff, v_eff = new["k"], new["v"]
        b, hkv, hd = x.shape[0], cfg.n_kv_heads, cfg.hd
        qg = q.reshape(b, hkv, cfg.n_heads // hkv, hd)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                       k_eff.astype(jnp.float32)) / np.sqrt(hd)
        mask = jnp.arange(MAX_LEN)[None] <= length[:, None]
        s = jnp.where(mask[:, None, None], s, -1e30)
        o = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, axis=-1),
                       v_eff.astype(jnp.float32))
        x = x + o.reshape(b, 1, -1).astype(x.dtype) @ p["attn"]["wo"]
        x = x + L.mlp(cfg, p["mlp"], L.apply_norm(cfg, p["norm2"], x))
        return x, new

    x = params["embed"][tokens][:, None]
    per = (params["positions"][0], cache["layers"][0])
    # the loop the program runs, so that bf16 rounds alike
    if cfg.static_unroll:
        news = []
        for i in range(lm.n_periods(cfg)):
            x, n = layer(x, jax.tree.map(lambda l: l[i], per))
            news.append(n)
        new = jax.tree.map(lambda *ls: jnp.stack(ls), *news)
    else:
        x, new = jax.lax.scan(layer, x, per)
    h = L.apply_norm(cfg, params["final_norm"], x)[:, 0]
    logits = h.astype(jnp.float32) @ \
        lm.unembed_matrix(cfg, params).astype(jnp.float32).T
    return logits, {"layers": [new], "length": length + 1}


@pytest.mark.parametrize("static_unroll", [False, True])
@pytest.mark.parametrize("kv", sorted(KV))
def test_decode_matches_blend(kv, static_unroll):
    """Bitwise the blend's cache at every position of every slot and layer
    (the new rows included, none written past the end); logits within f32
    rounding."""
    cfg = _cfg(kv, static_unroll)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = _random_cache(cfg, seed=1)
    tokens = jnp.asarray([3, 17, 42, 99, 200], jnp.int32)

    want_logits, want = jax.jit(
        lambda p, c, t: _blend_decode_step(cfg, p, c, t))(
        params, cache, tokens)
    logits, got = jax.jit(model.decode_step)(params, cache, tokens)

    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the rows were written where the slots' lengths point, and only there
    before = np.asarray(cache["layers"][0]["k"])
    after = np.asarray(got["layers"][0]["k"])
    changed = np.argwhere((before != after).any(axis=(0, 3, 4)))
    assert sorted(map(tuple, changed)) == [
        (slot, n) for slot, n in enumerate(LENGTHS) if n < MAX_LEN]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv", sorted(KV))
def test_fresh_cache_donates(kv):
    """``init_cache`` gives every leaf a buffer of its own, so a fresh cache
    goes straight into the donating jit, which consumes it."""
    model = build_model(_cfg(kv))
    params = model.init(jax.random.key(0))
    cache = model.init_cache(4, MAX_LEN)
    leaves = jax.tree.leaves(cache)
    assert len({id(l) for l in leaves}) == len(leaves)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    _, new = step(params, cache, jnp.zeros((4,), jnp.int32))
    jax.block_until_ready(new)
    assert all(l.is_deleted() for l in leaves)
    np.testing.assert_array_equal(np.asarray(new["length"]), 1)


def _requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=4, prompt=rng.integers(
        0, cfg.vocab_size, int(rng.integers(3, 12))).astype(np.int32))
        for i in range(n)]


@pytest.fixture(scope="module")
def batcher_setup():
    cfg = _cfg("bf16")
    return cfg, build_model(cfg).init(jax.random.key(0))


def test_batcher_donates_cache(batcher_setup):
    """After a step, the cache the batcher held before it is deleted: the
    decode program consumed it."""
    cfg, params = batcher_setup
    cb = ContinuousBatcher(cfg, n_slots=2, max_len=32, params=params)
    for r in _requests(cfg, 2, seed=0):
        cb.submit(r)
    cb._admit()                  # the step below admits nothing more
    before = jax.tree.leaves(cb.cache)
    cb.step()
    jax.block_until_ready(cb.cache)
    assert all(l.is_deleted() for l in before)
    assert not any(l.is_deleted() for l in jax.tree.leaves(cb.cache))


def test_batcher_tokens_match_undonated(batcher_setup):
    """The donating batcher serves the tokens an undonated decode serves."""
    cfg, params = batcher_setup
    outs = []
    for donate in (True, False):
        cb = ContinuousBatcher(cfg, n_slots=3, max_len=32, params=params)
        if not donate:
            cb._decode = jax.jit(cb.model.decode_step)
        reqs = _requests(cfg, 5, seed=3)
        for r in reqs:
            cb.submit(r)
        cb.run_until_drained()
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]
    assert all(len(o) == 4 for o in outs[0])
