"""The model layer's one scan path against the plain float32 reference
(``models/reference.py``), for the attention-only decoders it covers, dense
and MoE, at their smoke sizes in float32.

* Serving: ``Model.prefill`` of a prompt, then ``decode_step`` through the
  cache; every step's logits are the reference's at the same position.
* Training: ``Model.loss``'s cross-entropy is the reference's over the same
  tokens and labels.  The capacity dispatch drops pairs beyond an expert's
  capacity, which the reference never does, so the MoE configs get a
  capacity factor of ``n_experts``: every expert can then take every row.

Norm scales (q/k norms included) and QKV biases are moved off their init,
so that each term of the forward pass moves the result.  Both sides compute
in float32 on the CPU and differ only in the order of their sums (under
1e-6 when this was written), well under the 1e-5 they are held to.
"""

from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models import build_model, reference

ATOL = 1e-5
ref_logits = jax.jit(reference.logits, static_argnums=0)
SERVING = ["qwen2-7b", "llama3.2-3b", "gemma-2b", "phi3-medium-14b",
           "qwen2-moe-a2.7b"]
JITTERED = ("scale", "q_norm", "k_norm", "'bq'", "'bk'", "'bv'")


def _params(cfg, seed=0):
    params = build_model(cfg).init(jax.random.key(seed))
    key = jax.random.key(seed + 1)

    def jitter(path, leaf):
        name = jax.tree_util.keystr(path)
        if any(n in name for n in JITTERED):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            return leaf + 0.2 * jax.random.normal(k, leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(jitter, params)


@pytest.mark.parametrize("arch", SERVING)
def test_serving_matches_reference(arch):
    """A 24-token prompt prefilled, then 3 decode steps, each fed the
    reference's best token: every logits row within ATOL."""
    cfg = smoke_config(arch)
    assert cfg.compute_dtype == "float32"
    model = build_model(cfg)
    params = _params(cfg)
    rng = np.random.default_rng(zlib.crc32(arch.encode()))
    seq = [int(t) for t in rng.integers(0, cfg.vocab_size, 24)]

    logits, cache = jax.jit(model.prefill, static_argnums=2)(
        params, {"tokens": jnp.asarray([seq], jnp.int32)}, 32)
    decode = jax.jit(model.decode_step)
    for _ in range(3):
        want = np.asarray(ref_logits(cfg, params, np.asarray(seq)))[-1]
        np.testing.assert_allclose(np.asarray(logits)[0], want, rtol=0,
                                   atol=ATOL)
        seq.append(int(np.argmax(want)))
        logits, cache = decode(params, cache,
                               jnp.asarray(seq[-1:], jnp.int32))
    want = np.asarray(ref_logits(cfg, params, np.asarray(seq)))[-1]
    np.testing.assert_allclose(np.asarray(logits)[0], want, rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("arch", SERVING + ["qwen3-moe-235b-a22b"])
def test_training_forward_matches_reference(arch):
    """Two 32-token rows (two query chunks of the smoke configs' 16): the
    loss's ``ce`` is the mean reference cross-entropy within ATOL."""
    cfg = smoke_config(arch)
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    model = build_model(cfg)
    params = _params(cfg)
    rng = np.random.default_rng(zlib.crc32(arch.encode()))
    tokens = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)

    _, metrics = jax.jit(model.loss)(params, {"tokens": jnp.asarray(tokens),
                                              "labels": jnp.asarray(labels)})
    ce = []
    for row, lab in zip(tokens, labels):
        lg = np.asarray(ref_logits(cfg, params, row), np.float64)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        ce.append(lse - lg[np.arange(len(lab)), lab])
    assert abs(float(metrics["ce"]) - float(np.mean(ce))) <= ATOL
