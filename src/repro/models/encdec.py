"""Encoder-decoder transformer (seamless-m4t backbone).

Encoder: bidirectional self-attention stack over precomputed frame
embeddings (the speech frontend is a stub per the assignment).
Decoder: causal self-attention + cross-attention over encoder output + FFN.

Both stacks use layer-stacked params and ``lax.scan``; the decoder carries
self-attention KV caches plus per-layer cross K/V computed once from the
encoder output.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import layers as L
from .lm import _stack, chunked_ce_loss

Params = Dict[str, Any]


def init_encdec(cfg: ArchConfig, key) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    n_enc, n_dec = cfg.encoder_layers, cfg.n_layers
    keys = jax.random.split(key, n_enc + n_dec + 4)

    enc_layers = []
    for i in range(n_enc):
        ks = jax.random.split(keys[i], 2)
        enc_layers.append({
            "norm1": L.init_norm(cfg, cfg.d_model),
            "attn": L.init_attention(cfg, ks[0]),
            "norm2": L.init_norm(cfg, cfg.d_model),
            "mlp": L.init_mlp(cfg, ks[1]),
        })
    dec_layers = []
    for i in range(n_dec):
        ks = jax.random.split(keys[n_enc + i], 3)
        dec_layers.append({
            "norm1": L.init_norm(cfg, cfg.d_model),
            "attn": L.init_attention(cfg, ks[0]),
            "norm3": L.init_norm(cfg, cfg.d_model),
            "cross": L.init_attention(cfg, ks[1], cross=True),
            "norm2": L.init_norm(cfg, cfg.d_model),
            "mlp": L.init_mlp(cfg, ks[2]),
        })
    return {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dt),
        "encoder": _stack(enc_layers),
        "enc_final_norm": L.init_norm(cfg, cfg.d_model),
        "decoder": _stack(dec_layers),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }


def encode(cfg: ArchConfig, params: Params, embeds):
    """embeds (B, S_enc, d) -> encoder hidden states (B, S_enc, d)."""
    b, s, _ = embeds.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embeds.astype(jnp.dtype(cfg.compute_dtype))

    def body(x, p):
        def run(x):
            h = L.apply_norm(cfg, p["norm1"], x)
            x = x + L.attention(cfg, p["attn"], h, pos, causal=False)
            h = L.apply_norm(cfg, p["norm2"], x)
            return x + L.mlp(cfg, p["mlp"], h)
        if cfg.remat:
            run = jax.checkpoint(run)
        return run(x), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.apply_norm(cfg, params["enc_final_norm"], x)


def decode_train(cfg: ArchConfig, params: Params, tokens, enc_out):
    """Teacher-forced decoder pass.  tokens (B, S_dec) -> h (B, S_dec, d)."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = params["embed"][tokens]

    def body(x, p):
        def run(x):
            h = L.apply_norm(cfg, p["norm1"], x)
            x = x + L.attention(cfg, p["attn"], h, pos, causal=True)
            h = L.apply_norm(cfg, p["norm3"], x)
            kv = L.cross_kv(cfg, p["cross"], enc_out)
            x = x + L.cross_attention(cfg, p["cross"], h, kv)
            h = L.apply_norm(cfg, p["norm2"], x)
            return x + L.mlp(cfg, p["mlp"], h)
        if cfg.remat:
            run = jax.checkpoint(run)
        return run(x), None

    x, _ = jax.lax.scan(body, x, params["decoder"])
    return L.apply_norm(cfg, params["final_norm"], x)


def encdec_loss(cfg: ArchConfig, params: Params, batch):
    """batch: {'embeds' (B,S_enc,d), 'tokens' (B,S_dec), 'labels' (B,S_dec)}."""
    enc_out = encode(cfg, params, batch["embeds"])
    h = decode_train(cfg, params, batch["tokens"], enc_out)
    ce = chunked_ce_loss(cfg, params, h, batch["labels"])
    return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}


# --------------------------------------------------------------------- decode
def init_encdec_cache(cfg: ArchConfig, batch: int, max_len: int,
                      enc_len: int) -> Dict:
    cdt = jnp.dtype(cfg.compute_dtype)
    n_dec = cfg.n_layers
    kv = (n_dec, batch, max_len, cfg.n_kv_heads, cfg.hd)
    xkv = (n_dec, batch, enc_len, cfg.n_kv_heads, cfg.hd)
    # a buffer of its own per leaf, so that the cache can be donated
    return {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt),
            "xk": jnp.zeros(xkv, cdt), "xv": jnp.zeros(xkv, cdt),
            "length": jnp.zeros((batch,), jnp.int32)}


def encdec_prefill(cfg: ArchConfig, params: Params, embeds, tokens,
                   max_len: int):
    """Encode + teacher-forced prefill of the decoder prompt.

    Returns (last_logits, cache) with self- and cross-KV filled.
    """
    enc_out = encode(cfg, params, embeds)
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = params["embed"][tokens]

    def body(x, p):
        h = L.apply_norm(cfg, p["norm1"], x)
        y, (k, v) = L.attention(cfg, p["attn"], h, pos, causal=True,
                                kv_out=True)
        x = x + y
        h = L.apply_norm(cfg, p["norm3"], x)
        xk, xv = L.cross_kv(cfg, p["cross"], enc_out)
        x = x + L.cross_attention(cfg, p["cross"], h, (xk, xv))
        h = L.apply_norm(cfg, p["norm2"], x)
        x = x + L.mlp(cfg, p["mlp"], h)
        return x, (k, v, xk, xv)

    x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["decoder"])
    h = L.apply_norm(cfg, params["final_norm"], x)
    cdt = jnp.dtype(cfg.compute_dtype)
    pad = [(0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0)]
    cache = {"k": jnp.pad(ks.astype(cdt), pad),
             "v": jnp.pad(vs.astype(cdt), pad),
             "xk": xks.astype(cdt), "xv": xvs.astype(cdt),
             "length": jnp.full((b,), s, jnp.int32)}
    w = params["embed"]
    logits = h[:, -1].astype(jnp.float32) @ w.astype(jnp.float32).T
    return logits, cache


def encdec_decode_step(cfg: ArchConfig, params: Params, cache: Dict, tokens):
    """One decoder token with self-cache + cross-cache.  tokens (B,).

    The self-attention K/V ride in the scan's carry and each layer writes
    only its new rows, as in ``lm.decode_step``."""
    length = cache["length"]
    x = params["embed"][tokens][:, None]                # (B, 1, d)

    def body(carry, per):
        x, kv = carry
        p, cxk, cxv, i = per
        h = L.apply_norm(cfg, p["norm1"], x)
        y, kv = L.attention_decode(cfg, p["attn"], h, kv, length, layer=i)
        x = x + y
        h = L.apply_norm(cfg, p["norm3"], x)
        x = x + L.cross_attention(cfg, p["cross"], h, (cxk, cxv))
        h = L.apply_norm(cfg, p["norm2"], x)
        x = x + L.mlp(cfg, p["mlp"], h)
        return (x, kv), None

    (x, kv), _ = jax.lax.scan(
        body, (x, {"k": cache["k"], "v": cache["v"]}),
        (params["decoder"], cache["xk"], cache["xv"],
         jnp.arange(cfg.n_layers)))
    h = L.apply_norm(cfg, params["final_norm"], x)[:, 0]
    w = params["embed"]
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    new_cache = dict(cache, **kv, length=length + 1)
    return logits, new_cache
