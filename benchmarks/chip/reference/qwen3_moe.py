"""Plain float32 forward pass of Qwen3-MoE (arXiv:2505.09388; the Hugging
Face ``Qwen3MoeForCausalLM`` equations) on one chip's share of the experts,
written for the benchmark and independent of the program under test.

Per layer: RMSNorm; q, k, v projections (no bias); RMSNorm of each head of
q and k; rotary embedding (rotate-half, base ``rope_theta``); causal
grouped-query attention (query head ``i`` reads key/value head
``i // (heads / kv_heads)``), in blocks of ``Q_BLOCK`` queries; output
projection; residual.  RMSNorm, then the experts: a softmax over all
``expert_share["of"]`` router outputs, the top ``num_experts_per_tok``
weights, renormalised to sum 1 (``norm_topk_prob``); each held expert
``down(silu(gate x) * up x)`` is applied to every row and weighted by the
row's weight for it, 0 where the row did not pick it.  What the experts
that are not held would add is left out, as in the program.  Residual.
Then the final RMSNorm and the untied LM head.

Weights come in the layout of ``engines/moe_batcher.py`` in their served
dtype and are widened to float32; every product runs at
``Precision.HIGHEST``.  ``quant`` is the control of ``reference/qwen2.py``:
every projection, the router, the experts and the LM head on operands
rounded to int8 or fp8.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.qwen2 import HI, embed, matmul, rmsnorm, rope

Q_BLOCK = 512


def _attention(q, k, v):
    """Causal GQA of q (S, Hq, D) over k, v (S, Hkv, D), a block of queries
    at a time so that the scores of one block live at once."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    qb = min(Q_BLOCK, s)
    assert s % qb == 0, (s, qb)
    kpos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb).reshape(
            qb, hkv, hq // hkv, hd)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) / np.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI)
        return o.reshape(qb, hq * hd)

    return jax.lax.map(block, jnp.arange(s // qb)).reshape(s, hq * hd)


def experts(m: dict, p: dict, h, quant: Optional[str] = None):
    """The held experts' part of the layer's feed-forward over rows h (S, d)."""
    share = m["expert_share"]
    probs = jax.nn.softmax(matmul(h, p["router"], quant), axis=-1)
    w, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(y, e):
        g, u, dn, eid = e
        we = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)
        a = jax.nn.silu(matmul(h, g, quant)) * matmul(h, u, quant)
        return y + we[:, None] * matmul(a, dn, quant), None

    ids = share["first"] + jnp.arange(share["held"])
    return jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w_gate"], p["w_up"], p["w_down"], ids))[0]


def layer(m: dict, p: dict, x, quant: Optional[str] = None):
    """One decoder layer over a single sequence x (S, d) float32."""
    s = x.shape[0]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(s)

    h = rmsnorm(x, p["ln1"], eps)
    q = rmsnorm(matmul(h, p["wq"], quant).reshape(s, hq, hd), p["q_norm"],
                eps)
    k = rmsnorm(matmul(h, p["wk"], quant).reshape(s, hkv, hd), p["k_norm"],
                eps)
    v = matmul(h, p["wv"], quant).reshape(s, hkv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    x = x + matmul(_attention(q, k, v), p["wo"], quant)
    return x + experts(m, p, rmsnorm(x, p["ln2"], eps), quant)


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def _logits_at(mj, layers, head, tokens, rows, quant):
    m = _thawed(mj)

    def body(x, p):
        return layer(m, p, x, quant), None

    h = jax.lax.scan(body, embed(head, tokens), layers)[0][rows]
    hn = rmsnorm(h, head["final_norm"], m["rms_norm_eps"])
    return matmul(hn, head["lm_head"].T, quant)


def logits_at(m: dict, layers: dict, head: dict, tokens, rows,
              quant: Optional[str] = None):
    """The whole model over one sequence ``tokens`` (S,): logits (R, vocab)
    at positions ``rows`` (R,), in one program."""
    return _logits_at(_frozen(m), layers, head, tokens, rows, quant)


KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok",
        "norm_topk_prob")


def _frozen(m: dict):
    """The numbers of ``m`` the forward pass reads, hashable for jit."""
    share = m["expert_share"]
    return tuple((k, m[k]) for k in KEYS) + (
        ("expert_share", (share["first"], share["held"], share["of"])),)


def _thawed(mj) -> dict:
    m = dict(mj)
    first, held, of = m["expert_share"]
    m["expert_share"] = {"first": first, "held": held, "of": of}
    return m
