"""Plain float32 forward pass of the attention-only decoder models, dense or
MoE: the reference the tests hold the serving path to.

Written from the published equations (Qwen2, arXiv:2407.10671; Qwen3,
arXiv:2505.09388) and independent of :mod:`repro.models.layers`: ``jnp``
only, every product at ``default_matmul_precision("highest")``, one
sequence at a time, no cache and no batching.  It reads the program's
parameter tree (``lm.init_lm``'s layout) and widens every weight to float32.

Per layer: RMSNorm (eps 1e-6); q, k, v projections, plus their biases where
the tree has them; RMSNorm of each head of q and k (``qk_norm``);
rotate-half RoPE at base ``rope_theta``; causal grouped-query attention
(query head ``i`` reads key/value head ``i // (heads / kv_heads)``); output
projection; residual.  Then RMSNorm and the feed-forward: a gated MLP
``down(act(gate x) * up x)``, or a mixture of experts: a softmax router over
every published expert, the top ``k`` weights (renormalised to sum 1 where
``norm_topk_prob``), and each held expert applied to every row and weighted
by the row's routing weight for it (0 where the row did not pick it).  What
the experts that are not held would add is left out, as the program leaves
it out.  A shared expert is added under its sigmoid gate.  Residual.  Then
the final RMSNorm and the LM head (the embedding where tied).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rmsnorm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * _f32(scale)


def _rope(x, theta):
    """x (S, H, D) rotated by positions 0 .. S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(s)[:, None] * inv[None]              # (S, D/2)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(cfg: ArchConfig):
    return jax.nn.gelu if cfg.mlp_act == "gelu" else jax.nn.silu


def _attention(cfg: ArchConfig, p, x):
    s = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = (x @ _f32(p[n]) for n in ("wq", "wk", "wv"))
    if "bq" in p:
        q, k, v = q + _f32(p["bq"]), k + _f32(p["bk"]), v + _f32(p["bv"])
    q, k, v = (q.reshape(s, hq, hd), k.reshape(s, hkv, hd),
               v.reshape(s, hkv, hd))
    if cfg.qk_norm:
        q, k = _rmsnorm(q, p["q_norm"]), _rmsnorm(k, p["k_norm"])
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
    return o.reshape(s, hq * hd) @ _f32(p["wo"])


def _mlp(cfg: ArchConfig, p, x):
    return (_act(cfg)(x @ _f32(p["gate"])) * (x @ _f32(p["up"]))) \
        @ _f32(p["down"])


def _moe(cfg: ArchConfig, p, x):
    m = cfg.moe
    first, n = m.held
    probs = jax.nn.softmax(x @ _f32(p["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(n):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        h = _act(cfg)(x @ _f32(p["w_gate"][e])) * (x @ _f32(p["w_up"][e]))
        y = y + we[:, None] * (h @ _f32(p["w_down"][e]))
    if m.n_shared:
        gate = jax.nn.sigmoid(x @ _f32(p["shared_gate"]))
        y = y + gate * _mlp(cfg, p["shared"], x)
    return y


def logits(cfg: ArchConfig, params, tokens):
    """Logits (S, vocab) at every position of one sequence ``tokens`` (S,)."""
    if cfg.norm != "rmsnorm" or cfg.mrope_sections is not None or \
            "M" in (cfg.layer_period or "A"):
        raise ValueError(f"{cfg.name}: the reference covers RMSNorm, RoPE "
                         "and attention layers only")
    plen = len(cfg.layer_period or "A")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(tokens)]
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i // plen],
                             params["positions"][i % plen])
            x = x + _attention(cfg, p["attn"], _rmsnorm(x, p["norm1"]["scale"]))
            h = _rmsnorm(x, p["norm2"]["scale"])
            x = x + (_moe(cfg, p["moe"], h) if cfg.moe_layer(i)
                     else _mlp(cfg, p["mlp"], h))
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return _rmsnorm(x, params["final_norm"]["scale"]) @ _f32(head).T
