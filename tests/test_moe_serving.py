"""The serving path of a mixture of experts against the plain float32
reference (``models/reference.py``), at a small size of Qwen3-235B-A22B's
shape: GQA 16:1 with ``qk_norm``, 16 experts top-8 of which this chip holds
4, RoPE base 1e6, float32.

Tolerances: the program and the reference both compute in float32 on the
CPU, so they differ only in the order of their sums: a few units of float32
rounding on logits below 1 (3.3e-7 at the widest when this was written),
well under 1e-5.  The bf16 test shows the comparison is tight enough to
fail a lower precision: bf16 weights and activations miss the reference by
about 2e-2.
"""

from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import expert_share, get_arch
from repro.models import build_model, reference
from repro.models import layers as L
from repro.serve.scheduler import ContinuousBatcher, Request

LOGIT_ATOL = 1e-5
ref_logits = jax.jit(reference.logits, static_argnums=0)


def _cfg(first=4, n_held=4, dtype="float32", **moe):
    """qwen3-moe-235b-a22b's shape at a small width, 2 layers."""
    base = get_arch("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(
        base, n_layers=2, d_model=64, n_heads=16, n_kv_heads=1, head_dim=16,
        d_ff=32, vocab_size=128, param_dtype=dtype, compute_dtype=dtype,
        fsdp=False, moe=dataclasses.replace(base.moe, n_experts=16, d_ff=32,
                                            **moe))
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.moe.top_k, cfg.qk_norm,
            cfg.rope_theta) == (16, 8, True, 1e6)
    return expert_share(cfg, first, n_held) if n_held else cfg


def _params(cfg, seed=0):
    """The program's init with every norm scale (q/k norms included) moved
    off 1, so that each term of the forward pass moves the result."""
    params = build_model(cfg).init(jax.random.key(seed))
    key = jax.random.key(seed + 1)

    def jitter(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "q_norm" in name or "k_norm" in name:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            return leaf + 0.2 * jax.random.normal(k, leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(jitter, params)


def _serve(cfg, params, prompts, max_new, n_slots=4):
    """Serve ``prompts`` through the batcher; returns the requests and, per
    request, the logits row of each decode step that made one of its
    tokens."""
    cb = ContinuousBatcher(cfg, n_slots=n_slots, max_len=64, params=params)
    rows = {}
    dec = cb._decode

    def capture(p, c, t, live):
        out = dec(p, c, t, live)
        lg = np.asarray(out[0])
        for i, r in enumerate(cb.slots):
            if r is not None:
                rows.setdefault(r.rid, []).append(lg[i])
        return out
    cb._decode = capture
    reqs = [Request(rid=i, prompt=p, max_new=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    for r in reqs:
        cb.submit(r)
    cb.run_until_drained()
    return cb, reqs, {rid: np.stack(v) for rid, v in rows.items()}


def _worst_gap(cfg_ref, params_ref, reqs, rows):
    """The largest |program - reference| logit at any served position.  The
    batcher decodes from the last prompt token fed again, so the reference
    reads the prompt, that token and the served tokens but the last."""
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.prompt[-1:], r.out[:-1]])
        want = ref_logits(cfg_ref, params_ref, seq)[len(r.prompt):]
        assert want.shape == rows[r.rid].shape
        worst = max(worst, float(np.abs(rows[r.rid] - want).max()))
    return worst


PROMPTS = [13, 5, 29, 20, 9]
MAX_NEW = [6, 9, 4, 7, 5]


def _prompts(cfg, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in PROMPTS]


def test_batcher_matches_reference_at_every_served_position():
    """Prefill, then decode through the cache, in a 4-slot batcher that
    admits five requests as slots free up: every served position's logits
    are the reference's full forward pass over the same tokens."""
    cfg = _cfg()
    params = _params(cfg)
    _, reqs, rows = _serve(cfg, params, _prompts(cfg), MAX_NEW)
    assert [len(r.out) for r in reqs] == MAX_NEW
    assert _worst_gap(cfg, params, reqs, rows) < LOGIT_ATOL


def test_bf16_forward_fails_the_comparison():
    """The same weights served in bf16 miss the float32 reference by far
    more than the tolerance."""
    cfg, cfg16 = _cfg(), _cfg(dtype="bfloat16")
    params = _params(cfg)
    params16 = jax.tree.map(
        lambda x, y: x.astype(y.dtype), params,
        jax.eval_shape(build_model(cfg16).init, jax.random.key(0)))
    _, reqs, rows = _serve(cfg16, params16, _prompts(cfg), MAX_NEW)
    assert _worst_gap(cfg, params, reqs, rows) > 100 * LOGIT_ATOL


def _layer_inputs(cfg, seed=3, t=24):
    x = jax.random.normal(jax.random.key(seed), (t, cfg.d_model))
    return x.astype(jnp.dtype(cfg.compute_dtype))


@pytest.mark.parametrize("n_shared", [0, 1])
def test_disjoint_shares_sum_to_the_uncut_layer(n_shared):
    """Four chips holding experts 0-3, 4-7, 8-11 and 12-15 compute parts that
    add up to the layer holding all 16, with what every chip computes alike
    (a shared expert) counted once; their loads are the uncut layer's."""
    extra = dict(n_shared=n_shared, shared_d_ff=32 * n_shared)
    whole = _cfg(n_held=0, **extra)
    p = L.init_moe(whole, jax.random.key(5))
    x = _layer_inputs(whole)
    y, load = L.moe_dropless(whole, p, x)
    parts, loads = [], []
    for first in range(0, 16, 4):
        share = _cfg(first=first, n_held=4, **extra)
        ps = dict(p, **{n: p[n][first:first + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        ys, ls = L.moe_dropless(share, ps, x)
        parts.append(ys)
        loads.append(ls)
    shared = L._shared_expert(whole, p, x) if n_shared else 0.0
    got = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(y), atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(load))
    assert int(load.sum()) == x.shape[0] * whole.moe.top_k


def test_one_expert_takes_every_row_and_no_row_is_lost():
    """A router biased so that expert 0 is every row's first pick: the
    dropless layer gives each row the reference's result, where capacity
    dispatch (20 rows an expert at 32 rows, top-8 of 16) drops 12 of them."""
    cfg = _cfg(n_held=0)
    p = L.init_moe(cfg, jax.random.key(6))
    p["router"] = p["router"].at[:, 0].add(50.0)
    x = _layer_inputs(cfg, t=32) + 1.0          # every row leans to expert 0
    with jax.default_matmul_precision("highest"):
        want = reference._moe(cfg, p, x)
    y, load = L.moe_dropless(cfg, p, x)
    assert int(load[0]) == 32
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    yc, _ = L.moe(cfg, p, x[None])
    lost = np.abs(np.asarray(yc[0]) - np.asarray(want)).max(axis=-1) > 1e-3
    assert lost.sum() == 32 - 20


def test_idle_slots_and_padding_route_nothing():
    """One request in a 4-slot batcher, every expert held: each decode step
    routes k pairs per layer for the one live row, and the prefill routes
    k pairs per layer for each true prompt token, none for the bucket's
    padding."""
    cfg = _cfg(n_held=0)
    k, layers = cfg.moe.top_k, cfg.n_layers
    cb, reqs, _ = _serve(cfg, _params(cfg), _prompts(cfg)[:1], [6])
    st = cb.stats
    assert st["steps"] == 6 and st["prefill_padded_tokens"] == 16
    assert st["moe_pairs"] == 6 * 1 * k * layers
    assert st["moe_prefill_pairs"] == PROMPTS[0] * k * layers
    # one row picks k distinct experts in each layer
    assert st["moe_experts_hit"] == 6 * k * layers


def test_held_share_counts_only_held_experts():
    """A 4-of-16 share routes fewer pairs than every row's k, never more
    experts than it holds, and its program returns one load row per layer."""
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(cfg)
    cache = model.init_cache(3, 32)
    live = jnp.asarray([True, False, True])
    _, _, load = model.decode_step(params, cache, jnp.asarray([1, 2, 3]),
                                    live)
    assert load.shape == (cfg.n_layers, 4)
    assert 0 < int(load.sum()) <= 2 * 4 * cfg.n_layers


@pytest.mark.parametrize("norm", [True, False])
def test_norm_topk_prob_follows_the_config(norm):
    """Qwen3 renormalises its top-k weights; Qwen1.5-MoE (qwen2-moe-a2.7b)
    does not.  Both dispatch paths follow ``MoESpec.norm_topk_prob``."""
    assert get_arch("qwen3-moe-235b-a22b").moe.norm_topk_prob
    assert not get_arch("qwen2-moe-a2.7b").moe.norm_topk_prob
    cfg = _cfg(n_held=0, norm_topk_prob=norm)
    p = L.init_moe(cfg, jax.random.key(8))
    x = _layer_inputs(cfg, t=8)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(cfg, p, x)
    y, _ = L.moe_dropless(cfg, p, x)
    yc, _ = L.moe(cfg, p, x[None], capacity=8 * cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(yc[0]), np.asarray(want), atol=1e-5)
    other = _cfg(n_held=0, norm_topk_prob=not norm)
    assert np.abs(np.asarray(L.moe_dropless(other, p, x)[0] - y)).max() > 1e-3


def test_shares_are_refused_where_they_cannot_hold():
    with pytest.raises(ValueError):
        expert_share(_cfg(n_held=0), 14, 4)
    with pytest.raises(ValueError):
        L.moe(_cfg(), L.init_moe(_cfg(), jax.random.key(0)),
              _layer_inputs(_cfg())[None])


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, re, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro import sharding as sh
from repro.configs.base import smoke_config
from repro.launch.mesh import make_mesh
from repro.models import build_model

FF = 48                  # the experts' width: no other array has it
fsdp = sys.argv[1] == "fsdp"
base = smoke_config("qwen3-moe-235b-a22b")
cfg = dataclasses.replace(base, fsdp=fsdp, moe=dataclasses.replace(
    base.moe, n_experts=8, top_k=2, d_ff=FF))
model = build_model(cfg)
params = model.init(jax.random.key(0))
b, s = 8, 16
toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (b, 8)),
                   jnp.int32)
live_p = jnp.arange(8)[None] < jnp.asarray([8, 5, 8, 3, 8, 8, 1, 8])[:, None]
live = jnp.asarray([True, False] * 4)


def run(params):
    lg, cache, lp = jax.jit(lambda p, t, l: model.prefill(
        p, {"tokens": t}, s, l))(params, toks, live_p)
    lg2, _, ld = jax.jit(model.decode_step)(params, cache, toks[:, 0], live)
    return lg, lp, lg2, ld


want = run(params)
mesh = make_mesh((2, 4), ("data", "model"))
placed = jax.tree.map(lambda x, q: jax.device_put(x, NamedSharding(mesh, q)),
                      params, sh.param_specs(cfg, params, mesh))
with jax.set_mesh(mesh):
    got = run(placed)
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    hlo = jax.jit(model.decode_step).lower(
        placed, cache, toks[:, 0], live).compile().as_text()
for a, g in zip(want, got):
    assert g.shape == a.shape and float(jnp.max(jnp.abs(a - g))) < 1e-5, \
        (a.shape, float(jnp.max(jnp.abs(a - g))))
gathered = re.findall(r"= \w+\[([\d,]*)\]\S* all-gather\(", hlo)
assert not [g for g in gathered if str(FF) in g.split(",")], gathered
print("MESH_OK")
"""


@pytest.mark.parametrize("layout", ["ep", "fsdp"])
def test_mesh_serving_keeps_experts_on_their_devices(layout):
    """On a (2 data, 4 model) mesh, with the expert weights laid out by
    ``sharding.rules`` (experts over "model"; with FSDP also d_model over
    "data"), prefill and decode give the single-device logits and load
    (float32, so only the order of sums differs: under 1e-5), and the
    compiled decode step gathers no expert weights: each device runs the
    experts it holds (subprocess, 8 host devices)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT, layout], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
