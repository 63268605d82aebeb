"""Operations and bytes that the algorithm needs, computed from shapes and
true lengths alone: never from the program's cost analysis, so that a
change which removes work does not also lower the yardstick.

``m`` is a configuration file's dict (Hugging Face key names).  A matmul
of an (n, k) by a (k, m) operand counts 2*n*k*m operations.
"""

from __future__ import annotations

from typing import Iterable


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights that multiply activations in one decoder layer: the q, k, v
    and output projections and the gated MLP's three matrices."""
    d, ff, hd = m["hidden_size"], m["intermediate_size"], head_dim(m)
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def layer_param_count(m: dict) -> int:
    """Every parameter of one decoder layer: the matmul weights, the q, k, v
    biases and the two norm scales."""
    hd = head_dim(m)
    bias = (m["num_attention_heads"] + 2 * m["num_key_value_heads"]) * hd \
        if m.get("attention_bias", True) else 0
    return layer_matmul_params(m) + bias + 2 * m["hidden_size"]


def attention_pairs_prefill(n: int) -> int:
    """(query, key) pairs a causal prefill of ``n`` tokens scores."""
    return n * (n + 1) // 2


def forward_flops(m: dict, tokens: int, attn_pairs: int, head_rows: int,
                  layers: int = 0) -> float:
    """Operations of a forward pass through ``layers`` layers (default: the
    configuration's) over ``tokens`` tokens that score ``attn_pairs``
    (query, key) pairs in each layer, with the LM head applied to
    ``head_rows`` rows.  Attention counts q.k and p.v: 4 * heads * head_dim
    per pair and layer."""
    n_layers = layers or m["num_hidden_layers"]
    hq = m["num_attention_heads"] * head_dim(m)
    return (2.0 * n_layers * layer_matmul_params(m) * tokens
            + 4.0 * n_layers * hq * attn_pairs
            + 2.0 * m["vocab_size"] * m["hidden_size"] * head_rows)


def prefill_flops(m: dict, prompt_lens: Iterable[int]) -> float:
    """Model operations of prefilling each prompt at its true length, with
    the LM head on its last token only."""
    lens = list(prompt_lens)
    return forward_flops(m, sum(lens),
                         sum(attention_pairs_prefill(n) for n in lens),
                         len(lens))


def decode_flops(m: dict, context_lens: Iterable[int]) -> float:
    """Model operations of one decode step for live sequences whose caches
    hold ``context_lens`` positions before the step: each new token attends
    to its cache and itself, and gets its logits."""
    lens = list(context_lens)
    return forward_flops(m, len(lens), sum(n + 1 for n in lens), len(lens))


def weight_bytes(m: dict, dtype_bytes: int = 2) -> float:
    """Bytes of the weights a decode step reads once: every layer, the final
    norm and the LM head (the embedding is read by row; see
    :func:`decode_step_bytes`)."""
    d = m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else m["vocab_size"] * d
    return dtype_bytes * (m["num_hidden_layers"] * layer_param_count(m)
                          + d + head)


def kv_bytes_per_position(m: dict, dtype_bytes: int = 2) -> float:
    """Cache bytes of one position over all layers: K and V."""
    return (dtype_bytes * 2 * m["num_hidden_layers"]
            * m["num_key_value_heads"] * head_dim(m))


def decode_step_bytes(m: dict, context_lens: Iterable[int],
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step needs to move: the weights once, one embedding
    row per live sequence, and the K/V of every live position of every live
    sequence, the new one included (read or written once)."""
    lens = list(context_lens)
    return (weight_bytes(m, dtype_bytes)
            + dtype_bytes * m["hidden_size"] * len(lens)
            + kv_bytes_per_position(m, dtype_bytes) * sum(n + 1 for n in lens))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 rate and bytes over peak HBM bandwidth."""
    return max(flops / peak["bf16_flop_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
