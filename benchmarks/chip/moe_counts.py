"""Operations and bytes of a mixture-of-experts configuration that the
algorithm needs, from shapes, true lengths and the routed load alone (the
pairs routed to held experts, and the held experts that took any): never
from the program's cost analysis.

``m`` is a configuration file's dict (Hugging Face key names, as in
``configs/qwen3-moe-235b-d8-e16.json``): every layer is an expert layer,
``num_experts`` experts are held here of ``expert_share["of"]`` that the
router scores, and weights are served in ``torch_dtype`` (2 bytes).  A
matmul of an (n, k) by a (k, m) operand counts 2*n*k*m operations; an
expert is three such matrices, so a routed pair costs 6*d*f.
"""

from __future__ import annotations

from typing import Iterable

from benchmarks.chip.counts import attention_pairs_prefill, head_dim

DTYPE_BYTES = 2


def attention_params(m: dict) -> int:
    """Weights that multiply every token in one layer: the q, k, v and
    output projections and the router over every published expert."""
    d, hd = m["hidden_size"], head_dim(m)
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + d * m["expert_share"]["of"]


def expert_params(m: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_flops(m: dict, pairs: float) -> float:
    """Operations of ``pairs`` (row, expert) pairs: 6*d*f each."""
    return 2.0 * expert_params(m) * pairs


def dense_flops(m: dict, tokens: int, attn_pairs: int, head_rows: int
                ) -> float:
    """Every operation but the experts': the projections and router over
    ``tokens``, q.k and p.v over ``attn_pairs`` (query, key) pairs a layer
    (4 * heads * head_dim each), and the LM head over ``head_rows``."""
    n = m["num_hidden_layers"]
    hq = m["num_attention_heads"] * head_dim(m)
    return (2.0 * n * attention_params(m) * tokens
            + 4.0 * n * hq * attn_pairs
            + 2.0 * m["vocab_size"] * m["hidden_size"] * head_rows)


def decode_flops(m: dict, context_lens: Iterable[int], pairs: float
                 ) -> float:
    """One decode step of sequences whose caches hold ``context_lens``
    positions before it, whose rows routed ``pairs`` pairs to held experts
    over all layers."""
    lens = list(context_lens)
    return (dense_flops(m, len(lens), sum(n + 1 for n in lens), len(lens))
            + expert_flops(m, pairs))


def prefill_flops(m: dict, prompt_lens: Iterable[int], pairs: float) -> float:
    """Prefills of prompts at their true lengths (the LM head on each last
    token), whose rows routed ``pairs`` pairs to held experts."""
    lens = list(prompt_lens)
    return (dense_flops(m, sum(lens),
                        sum(attention_pairs_prefill(n) for n in lens),
                        len(lens))
            + expert_flops(m, pairs))


def layer_shared_bytes(m: dict) -> float:
    """Bytes of one layer's weights that every step reads: attention, the
    router, two norm scales and the q and k norm scales."""
    d, hd = m["hidden_size"], head_dim(m)
    return DTYPE_BYTES * (attention_params(m) + 2 * d + 2 * hd)


def kv_bytes_per_position(m: dict) -> float:
    """Cache bytes of one position over all layers: K and V."""
    return (DTYPE_BYTES * 2 * m["num_hidden_layers"]
            * m["num_key_value_heads"] * head_dim(m))


def decode_step_bytes(m: dict, context_lens: Iterable[int],
                      experts_hit: float) -> float:
    """Bytes one decode step needs to move: each layer's attention and
    router once, the weights of each held (layer, expert) that took a pair
    once (``experts_hit`` of them), the final norm and LM head, one
    embedding row per live sequence, and the K/V of every live position,
    the new one included."""
    lens = list(context_lens)
    d = m["hidden_size"]
    return (m["num_hidden_layers"] * layer_shared_bytes(m)
            + DTYPE_BYTES * expert_params(m) * experts_hit
            + DTYPE_BYTES * (d + m["vocab_size"] * d)
            + DTYPE_BYTES * d * len(lens)
            + kv_bytes_per_position(m) * sum(n + 1 for n in lens))


def gemm_bytes(m: dict, pairs: float, experts_hit: float) -> float:
    """Bytes the grouped matmuls of a step need: each expert hit's weights
    once, and each routed row in and out of the gate, up and down
    projections (d in, f out; d in, f out; f in, d out)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return DTYPE_BYTES * (expert_params(m) * experts_hit
                          + 3 * (d + f) * pairs)
