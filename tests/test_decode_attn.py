"""The Pallas decode attention ``kernels.decode_attn`` against the XLA path
it replaced on one device (``layers._decode_attn_xla``), on a cache stacked
over periods as ``lm.init_cache`` lays it out; then the decode step and the
batcher on either path.

Tolerances: the kernel computes in float32 as the XLA path does, but its
output takes q's dtype, bf16 here, and its softmax runs block by block, so
its rows differ from the XLA path's float32 by bf16 rounding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import expert_share, get_arch, smoke_config
from repro.kernels.decode_attn import decode_attn
from repro.models import build_model, lm
from repro.models import layers as L
from repro.serve.scheduler import ContinuousBatcher, Request

S, BK, D, P = 32, 8, 128, 3
# an empty slot, one mid-block, the last position, and idle slots past the
# end (a freed slot's length keeps growing), each twice
LENGTHS = (0, 13, S - 1, S, S + 3) * 2
# Hq/Hkv of the two served cells: qwen2-7b (G 7) and the qwen3-moe share (16)
GROUPS = {"qwen2-7b": (28, 4), "qwen3-moe": (64, 4)}
LIVE = {"all": (True,) * 10,
        "mixed": (True, False, True, True, False, False, True, False,
                  True, True),
        "none-given": None}
TOL = 1e-2


def _case(hq, hkv, seed, s=S):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((len(LENGTHS), hq, D)), jnp.bfloat16)
    kv = [jnp.asarray(rng.standard_normal((P, len(LENGTHS), s, hkv, D)),
                      jnp.bfloat16) for _ in range(2)]
    return q, kv[0], kv[1], jnp.asarray(LENGTHS, jnp.int32)


def _xla(q, k, v, layer, length, live=None):
    """The XLA path, called as the kernel is."""
    return L._decode_attn_xla({"k": k, "v": v}, q, layer, length, live)


def _check(got, want, live):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    live = np.ones(len(got), bool) if live is None else np.asarray(live)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    assert not got[~live].any(), "a slot outside live must give zeros"


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("layer", [0, P - 1])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_decode_attn_matches_xla(group, layer, live):
    """Every length, from an empty slot to idle slots past the end, at
    each cell's head grouping, in the first and the last period."""
    q, k, v, length = _case(*GROUPS[group], seed=layer)
    mask = None if LIVE[live] is None else jnp.asarray(LIVE[live])
    got = decode_attn(q, k, v, layer, length, mask, bk=BK)
    assert got.shape == q.shape and got.dtype == q.dtype
    _check(got, _xla(q, k, v, layer, length, mask), LIVE[live])


# S + 4: the cache's last block is ragged, and starts inside the one before
@pytest.mark.parametrize("s", [S, S + 4])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_decode_attn_reads_only_live_blocks(group, s):
    """NaN everywhere the kernel must not read: other periods, the blocks
    past each live slot's last one, and every block of a slot outside
    ``live``.  The output has none, and matches the clean cache's."""
    q, k, v, length = _case(*GROUPS[group], seed=5, s=s)
    live = np.asarray(LIVE["mixed"])
    layer = 1
    pos = np.arange(s)
    last = np.minimum(np.asarray(LENGTHS), s - 1) // BK
    unread = np.ones((P, len(LENGTHS), s), bool)
    unread[layer] = ~live[:, None] | (pos[None] // BK > last[:, None])
    assert unread[layer].any() and not unread[layer].all()

    def poison(c):
        return jnp.where(jnp.asarray(unread)[..., None, None], jnp.nan, c)

    got = decode_attn(q, poison(k), poison(v), layer, length,
                      jnp.asarray(live), bk=BK)
    assert not np.isnan(np.asarray(got, np.float32)).any()
    _check(got, _xla(q, k, v, layer, length, jnp.asarray(live)), live)


# ------------------------------------------------------------ model level
MAX_LEN = 16
STEP_LENGTHS = (0, 7, MAX_LEN - 1, MAX_LEN, MAX_LEN + 3)
STEP_LIVE = (True, False, True, True, False)


# a head of 128 lanes and two KV heads: a head shape the kernel takes
# (``reads_in_place``), as the served models' 128 and four are


def _qwen2():
    return dataclasses.replace(smoke_config("qwen2-7b"), n_layers=3,
                               head_dim=D, param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def _qwen3_moe_share():
    """qwen3-moe-235b-a22b's shape at a small width: 16:1 GQA with q/k
    norms, 16 experts top-8, 4 of them held."""
    base = get_arch("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(
        base, n_layers=2, d_model=64, n_heads=32, n_kv_heads=2, head_dim=D,
        d_ff=32, vocab_size=128, param_dtype="bfloat16",
        compute_dtype="bfloat16", fsdp=False,
        moe=dataclasses.replace(base.moe, n_experts=16, d_ff=32))
    return expert_share(cfg, 4, 4)


CFGS = {"qwen2-7b": _qwen2, "qwen3-moe-share": _qwen3_moe_share}


def _filled_cache(cfg, seed):
    """Every position filled, so a stray read or write shows."""
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, len(STEP_LENGTHS),
                                                 MAX_LEN))
    rng = np.random.default_rng(seed)
    layers = jax.tree.map(lambda l: jnp.asarray(
        rng.standard_normal(l.shape), l.dtype), cache["layers"])
    return {"layers": layers, "length": jnp.asarray(STEP_LENGTHS, jnp.int32)}


def _step(model):
    # a fresh function for each path: jit's trace cache is keyed on it
    return jax.jit(lambda p, c, t, live: model.decode_step(p, c, t, live))


def _counted(monkeypatch):
    """Count the kernel's calls from ``layers``, so that a test shows the
    kernel's path was the one taken."""
    calls = []

    def kernel(*args, **kw):
        calls.append(1)
        return decode_attn(*args, **kw)

    monkeypatch.setattr(L, "decode_attn", kernel)
    return calls


@pytest.mark.parametrize("name", sorted(CFGS))
def test_attention_decode_writes_the_same_cache(name, monkeypatch):
    """One layer's decode on either path, from the same input: the written
    cache bit for bit (the same scatter of the same rows), the output to
    bf16 rounding on the live rows."""
    cfg = CFGS[name]()
    params = build_model(cfg).init(jax.random.key(0))
    p = jax.tree.map(lambda l: l[1], params["positions"][0]["attn"])
    cache = _filled_cache(cfg, 1)
    x = jax.random.normal(jax.random.key(3), (len(STEP_LENGTHS), 1,
                                              cfg.d_model), jnp.bfloat16)
    live = jnp.asarray(STEP_LIVE)

    def run():
        return jax.jit(lambda x, c, n: L.attention_decode(
            cfg, p, x, c, n, 1, live))(x, cache["layers"][0],
                                       cache["length"])

    calls = _counted(monkeypatch)
    y, got = run()
    assert calls, "the kernel's path was not taken"
    monkeypatch.setattr(L, "decode_attn", _xla)
    want_y, want = run()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    rows = np.asarray(STEP_LIVE)
    ref = np.asarray(want_y, np.float32)[rows]
    np.testing.assert_allclose(np.asarray(y, np.float32)[rows], ref,
                               rtol=TOL, atol=TOL * np.abs(ref).max())


@pytest.mark.parametrize("name", sorted(CFGS))
def test_decode_step_kernel_matches_xla(name, monkeypatch):
    """The whole decode step on the kernel against the XLA path: the cache
    outside the new rows untouched on both; the new rows bit for bit in the
    first period, and to bf16 rounding in later ones, which follow the
    attention above them; the live rows' logits to bf16 rounding, with the
    same argmax."""
    cfg = CFGS[name]()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.asarray([3, 17, 42, 99, 100], jnp.int32) % cfg.vocab_size
    live = jnp.asarray(STEP_LIVE)
    before = _filled_cache(cfg, 1)
    calls = _counted(monkeypatch)
    logits, cache, _ = _step(model)(params, _filled_cache(cfg, 1), tokens,
                                    live)
    assert calls, "the kernel's path was not taken"
    monkeypatch.setattr(L, "decode_attn", _xla)
    want_logits, want, _ = _step(model)(params, _filled_cache(cfg, 1),
                                        tokens, live)
    np.testing.assert_array_equal(np.asarray(cache["length"]),
                                  np.asarray(want["length"]))
    rows = np.asarray(STEP_LIVE)
    new = np.zeros((len(STEP_LENGTHS), MAX_LEN), bool)
    for slot, n in enumerate(STEP_LENGTHS):
        if n < MAX_LEN:
            new[slot, n] = True
    for b, g, w in zip(*(jax.tree.leaves(c["layers"])
                         for c in (before, cache, want))):
        b, g, w = (np.asarray(a, np.float32) for a in (b, g, w))
        np.testing.assert_array_equal(g[:, ~new], b[:, ~new])
        np.testing.assert_array_equal(w[:, ~new], b[:, ~new])
        np.testing.assert_array_equal(g[0, new], w[0, new])
        np.testing.assert_allclose(g[:, new], w[:, new], rtol=TOL,
                                   atol=TOL * np.abs(w[:, new]).max())
    got, ref = np.asarray(logits)[rows], np.asarray(want_logits)[rows]
    np.testing.assert_allclose(got, ref, rtol=TOL,
                               atol=TOL * np.abs(ref).max())
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))


@pytest.mark.parametrize("name", sorted(CFGS))
def test_batcher_tokens_match_xla_path(name, monkeypatch):
    """Requests through the batcher, slots freed and reused, get the same
    tokens on the kernel and on the XLA path."""
    cfg = CFGS[name]()
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(3, 20, 5)]

    def serve():
        cb = ContinuousBatcher(cfg, n_slots=3, max_len=32, params=params)
        reqs = [Request(rid=i, prompt=p, max_new=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            cb.submit(r)
        cb.run_until_drained()
        return [r.out for r in reqs]

    calls = _counted(monkeypatch)
    got = serve()
    assert calls, "the kernel's path was not taken"
    monkeypatch.setattr(L, "decode_attn", _xla)
    assert serve() == got
    assert all(len(o) == 6 for o in got)


# (Hkv, D, dtype): the served models' heads, an 8-row multiple, f32 at one
# KV head; then shapes a TPU lays out with S inward (one bf16 KV head, ten
# heads, a 64-lane head), which keep the XLA path
LAYOUTS = [((4, 128, "bfloat16"), True), ((2, 256, "bfloat16"), True),
           ((24, 128, "bfloat16"), True), ((1, 128, "float32"), True),
           ((1, 256, "bfloat16"), False), ((10, 128, "bfloat16"), False),
           ((16, 64, "bfloat16"), False), ((12, 128, "float32"), False)]


@pytest.mark.parametrize("shape,kernel", LAYOUTS)
def test_attention_decode_takes_kernel_where_cache_is_in_rows(
        shape, kernel, monkeypatch):
    """The kernel only where a TPU keeps the cache's (Hkv, D) in rows (the
    layouts ``tests/test_tpu_compile.py`` compiles for a described v5e);
    elsewhere its operand would be a relaid copy of the whole cache, and
    the XLA path attends."""
    hkv, d, dtype = shape
    cfg = dataclasses.replace(smoke_config("qwen2-7b"), n_heads=2 * hkv,
                              n_kv_heads=hkv, head_dim=d, param_dtype=dtype,
                              compute_dtype=dtype)
    p = L.init_attention(cfg, jax.random.key(0))
    cache = {n: jnp.zeros((2, 2, 8, hkv, d), dtype) for n in ("k", "v")}
    x = jnp.ones((2, 1, cfg.d_model), dtype)
    calls = _counted(monkeypatch)
    L.attention_decode(cfg, p, x, cache, jnp.asarray([3, 9]), 1)
    assert bool(calls) == kernel
