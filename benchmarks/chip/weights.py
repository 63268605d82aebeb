"""The benchmark's own weights: drawn on the device from the seed, in one
jitted call, in the dtype the configuration serves them in
(``torch_dtype``).

The layout is the benchmark's, named after the Hugging Face Qwen2 modules
and stacked over layers (leading axes ``lead``); an engine maps it onto
the program's parameter tree, and the reference reads it as it is.  Layer
``l`` (flat index) draws from ``fold_in(key, l)`` alone, so any block of
layers can be drawn again on its own, bit for bit.  Keys enter the jitted
draw as arguments, so one compiled program serves every seed.

Scales: projections N(0, 1/fan_in), q/k/v biases N(0, 0.02^2), norm scales
1 + N(0, 0.1^2), embedding and LM head N(0, 0.02^2), so that every term of
the forward pass, biases and norm scales included, moves the result.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.counts import head_dim

HEAD_STREAM = 1 << 20


def base_key(seed: int):
    """The root key of ``seed``; seeds of more than 32 bits keep their high
    bits (``jax.random.key`` alone would drop them)."""
    return jax.random.fold_in(jax.random.key(seed % (1 << 32)), seed >> 32)


def layer_shapes(m: dict) -> dict:
    d, ff, hd = m["hidden_size"], m["intermediate_size"], head_dim(m)
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return {"ln1": (d,), "ln2": (d,),
            "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "bq": (q,), "bk": (kv,), "bv": (kv,),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def _dtype(m: dict):
    return jnp.dtype(m["torch_dtype"])


def _draw_layer(m: dict, key):
    dtype = _dtype(m)
    shapes = layer_shapes(m)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("ln1", "ln2"):
            x = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            x = 0.02 * z
        else:
            x = z / np.sqrt(shape[0])
        out[name] = x.astype(dtype)
    return out


def layer_keys(seed: int, first: int, lead: Sequence[int]) -> jax.Array:
    """Raw key data (uint32) of layers ``first .. first + prod(lead) - 1``,
    shaped ``lead + (2,)`` (row-major over the flat layer index)."""
    n = int(np.prod(lead))
    root = base_key(seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(root, i))(
        jnp.arange(first, first + n))
    return jax.random.key_data(keys).reshape(tuple(lead) + (-1,))


def draw_layers(m: dict, key_data) -> dict:
    """The layers of ``key_data`` (from :func:`layer_keys`), stacked over its
    leading axes; traceable, so it runs inside the caller's jit."""
    fn = lambda kd: _draw_layer(m, jax.random.wrap_key_data(kd))
    for _ in range(key_data.ndim - 1):
        fn = jax.vmap(fn)
    return fn(key_data)


def head_key(seed: int) -> jax.Array:
    """Raw key data (uint32) of the embedding, final norm and LM head."""
    return jax.random.key_data(jax.random.fold_in(base_key(seed),
                                                  HEAD_STREAM))


def draw_head(m: dict, key_data) -> dict:
    """Embedding, final norm scale and (untied) LM head from
    :func:`head_key`; traceable."""
    d, v, dtype = m["hidden_size"], m["vocab_size"], _dtype(m)
    k = jax.random.split(jax.random.wrap_key_data(key_data), 3)
    out = {"embed": (0.02 * jax.random.normal(k[0], (v, d))).astype(dtype),
           "final_norm": (1.0 + 0.1 * jax.random.normal(k[1], (d,))
                          ).astype(dtype)}
    if not m["tie_word_embeddings"]:
        out["lm_head"] = (0.02 * jax.random.normal(k[2], (v, d))
                          ).astype(dtype)
    return out


def draw(m: dict, seed: int, lead: Sequence[int]):
    """``(layers, head)`` of the whole configuration in one jitted call.
    ``lead`` splits the layer axis (``(L,)``, or ``(stages, L/stages)``)."""
    assert int(np.prod(lead)) == m["num_hidden_layers"], lead
    fn = lambda kd, hk: (draw_layers(m, kd), draw_head(m, hk))
    return jax.jit(fn)(layer_keys(seed, 0, lead), head_key(seed))
