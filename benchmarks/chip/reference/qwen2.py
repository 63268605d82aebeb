"""Plain float32 forward pass of Qwen2 (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM`` equations), written for the benchmark and independent
of the program under test.

Per layer: RMSNorm, q/k/v projections with bias, rotary embedding
(rotate-half, base ``rope_theta``), causal grouped-query attention (query
head ``i`` reads key/value head ``i // (heads / kv_heads)``), output
projection, residual; RMSNorm, SwiGLU MLP ``down(silu(gate x) * up x)``,
residual.  Then the final RMSNorm and the untied LM head.

Weights come in the benchmark's layout (``benchmarks/chip/weights.py``) in
their served dtype and are widened to float32; every product runs at
``Precision.HIGHEST``, so no operand is rounded to bfloat16 on a TPU.

``quant`` computes the same pass in a lower precision, for the control
that has to fail the comparison: ``"int8"`` rounds every projection's
activations per row and weights per output column to symmetric int8, and
``"fp8"`` to float8_e4m3fn with the same scaling; attention's own products
stay in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _round(x, axis, quant):
    """``x`` rounded to ``quant`` with one symmetric scale per slice along
    ``axis`` (the contracted axis), returned in float32."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    if quant == "int8":
        q = jnp.clip(jnp.round(x / scale), -127, 127)
    else:
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def matmul(x, w, quant: Optional[str] = None):
    """``x @ w`` in float32 at HIGHEST; with ``quant``, of the rounded
    operands."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant is not None:
        x = _round(x, -1, quant)
        w = _round(w, 0, quant)
    return jnp.dot(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope(x, pos, theta):
    """x (S, H, D) rotated by positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(m: dict, p: dict, x, quant: Optional[str] = None):
    """One decoder layer over a single sequence x (S, d) float32."""
    s = x.shape[0]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // hq
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(s)

    h = rmsnorm(x, p["ln1"], eps)
    q = (matmul(h, p["wq"], quant) + p["bq"].astype(jnp.float32)
         ).reshape(s, hq, hd)
    k = (matmul(h, p["wk"], quant) + p["bk"].astype(jnp.float32)
         ).reshape(s, hkv, hd)
    v = (matmul(h, p["wv"], quant) + p["bv"].astype(jnp.float32)
         ).reshape(s, hkv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI)
    x = x + matmul(o.reshape(s, hq * hd), p["wo"], quant)

    h = rmsnorm(x, p["ln2"], eps)
    g = jax.nn.silu(matmul(h, p["gate"], quant)) * matmul(h, p["up"], quant)
    return x + matmul(g, p["down"], quant)


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def _stack(mj, layers, x, quant):
    m = dict(mj)

    def body(x, p):
        return layer(m, p, x, quant), None

    return jax.lax.scan(body, x, layers)[0]


def run_layers(m: dict, layers: dict, x, quant: Optional[str] = None):
    """The stacked ``layers`` (leading axis: layer) over x (S, d)."""
    return _stack(_frozen(m), layers, x.astype(jnp.float32), quant)


def _head(m: dict, head: dict, h, quant):
    w = head["embed"] if m["tie_word_embeddings"] else head["lm_head"]
    hn = rmsnorm(h, head["final_norm"], m["rms_norm_eps"])
    return matmul(hn, w.T, quant)


def embed(head: dict, tokens):
    """Rows of the embedding for ``tokens`` (S,), in float32."""
    return head["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def _logits_at(mj, layers, head, tokens, rows, quant):
    m = dict(mj)

    def body(x, p):
        return layer(m, p, x, quant), None

    h = jax.lax.scan(body, embed(head, tokens), layers)[0]
    return _head(m, head, h[rows], quant)


def logits_at(m: dict, layers: dict, head: dict, tokens, rows,
              quant: Optional[str] = None):
    """The whole model over one sequence ``tokens`` (S,): logits (R, vocab)
    at positions ``rows`` (R,), in one program."""
    return _logits_at(_frozen(m), layers, head, tokens, rows, quant)


def _frozen(m: dict):
    """The numbers of ``m`` the forward pass reads, hashable for jit."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return tuple((k, m[k]) for k in keys)
