"""Decoder-only / hybrid language model over period-stacked parameters.

An architecture is a repeating *period* of layer kinds (e.g. jamba's
``MMMMAMMM`` with MoE on odd positions).  Parameters for each position within
the period are stacked across periods along a leading axis.  The model body
runs two ways: the training forward, a pipeline stage and the decode step
are each one ``lax.scan`` over periods whose body unrolls the period's
positions — HLO size stays O(period), not O(n_layers), which keeps 94-layer
configs compilable at 512 devices — and the serving prefill unrolls every
period, each layer handing on its cache entry.

Caches for decode mirror the same structure: per period-position, leaves
stacked over periods.  Decode carries the stacked K/V through its scan and
writes one row per sequence, so a donated cache is updated in place.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import layers as L

Params = Dict[str, Any]


# ------------------------------------------------------------------ structure
def period_structure(cfg: ArchConfig) -> List[Dict[str, str]]:
    """Per position within one period: mixer kind + ffn kind."""
    pat = cfg.layer_period or "A"
    out = []
    for i, kind in enumerate(pat):
        out.append({
            "mixer": "attn" if kind == "A" else "mamba",
            "ffn": "moe" if cfg.moe_layer(i) else "dense",
        })
    return out


def n_periods(cfg: ArchConfig) -> int:
    plen = len(cfg.layer_period or "A")
    assert cfg.n_layers % plen == 0
    return cfg.n_layers // plen


# ----------------------------------------------------------------------- init
def _stack(leaves: List[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)


def init_position(cfg: ArchConfig, key, spec: Dict[str, str],
                  cross: bool = False) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {"norm1": L.init_norm(cfg, cfg.d_model),
                 "norm2": L.init_norm(cfg, cfg.d_model)}
    if spec["mixer"] == "attn":
        p["attn"] = L.init_attention(cfg, ks[0])
    else:
        p["mamba"] = L.init_mamba(cfg, ks[0])
    if spec["ffn"] == "moe":
        p["moe"] = L.init_moe(cfg, ks[1])
    else:
        p["mlp"] = L.init_mlp(cfg, ks[1])
    if cross:
        p["cross"] = L.init_attention(cfg, ks[2], cross=True)
        p["norm3"] = L.init_norm(cfg, cfg.d_model)
    return p


def init_lm(cfg: ArchConfig, key) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    struct = period_structure(cfg)
    np_ = n_periods(cfg)
    keys = jax.random.split(key, np_ * len(struct) + 3)
    positions = []
    for pos_i, spec in enumerate(struct):
        per_period = [init_position(cfg, keys[per * len(struct) + pos_i],
                                    spec)
                      for per in range(np_)]
        positions.append(_stack(per_period))
    params: Params = {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dt),
        "positions": positions,
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[-2], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt)
    return params


# -------------------------------------------------------------------- forward
def _mixer(cfg: ArchConfig, spec: Dict[str, str], p: Params, x, pos,
           kv_out: bool = False):
    """Pre-norm mixer and its residual.  Returns (x, extras): with
    ``kv_out``, the layer's K/V (attention) or (conv, ssm) state (mamba)."""
    extras = None
    h = L.apply_norm(cfg, p["norm1"], x)
    if spec["mixer"] == "attn":
        if kv_out:
            y, extras = L.attention(cfg, p["attn"], h, pos, kv_out=True)
        else:
            y = L.attention(cfg, p["attn"], h, pos)
    else:
        if kv_out:
            y, extras = L.mamba(cfg, p["mamba"], h, return_state=True)
        else:
            y = L.mamba(cfg, p["mamba"], h)
    return x + y, extras


def _position_block(cfg: ArchConfig, spec: Dict[str, str], p: Params, x,
                    pos):
    """One layer of the training forward: mixer, then the ffn, whose MoE
    dispatches by capacity.  Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    x, _ = _mixer(cfg, spec, p, x, pos)
    h = L.apply_norm(cfg, p["norm2"], x)
    if spec["ffn"] == "moe":
        y, aux = L.moe(cfg, p["moe"], h)
    else:
        y = L.mlp(cfg, p["mlp"], h)
    return x + y, aux


def _serve_ffn(cfg: ArchConfig, spec: Dict[str, str], p: Params, x, live,
               stacked: Params, layer):
    """Pre-norm ffn and its residual in the serving programs, for the layer
    whose parameters are ``p``, period ``layer`` of the position's
    ``stacked`` ones: MoE routes dropless over the held experts, only the
    rows ``live`` marks (None: all), its kernels reading the expert weights
    from ``stacked`` in place.  Returns (x, load): the pairs each held
    expert took, or None."""
    h = L.apply_norm(cfg, p["norm2"], x)
    if spec["ffn"] != "moe":
        return x + L.mlp(cfg, p["mlp"], h), None
    b, s, d = h.shape
    y, load = L.moe_dropless(cfg, stacked["moe"], h.reshape(b * s, d),
                             None if live is None else live.reshape(-1),
                             layer)
    return x + y.reshape(b, s, d), load


def _no_load(cfg: ArchConfig):
    return jnp.zeros((0, cfg.moe.held[1] if cfg.moe else 0), jnp.int32)


def backbone(cfg: ArchConfig, params: Params, x, pos):
    """The training forward: x (B, S, d) -> (h (B, S, d), aux_loss), one
    scan over periods."""
    struct = period_structure(cfg)

    def period_run(x, period_params):
        a_total = jnp.zeros((), jnp.float32)
        for spec, p in zip(struct, period_params):
            x, a = _position_block(cfg, spec, p, x, pos)
            a_total = a_total + a
        return x, a_total

    if cfg.remat:
        period_run = jax.checkpoint(period_run)

    def period_body(carry, period_params):
        x, aux = carry
        x, a = period_run(x, period_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        period_body, (x, jnp.zeros((), jnp.float32)), params["positions"])
    return L.apply_norm(cfg, params["final_norm"], x), aux


def run_stack(cfg: ArchConfig, positions, x, pos):
    """Apply the period stack only (no embed / final norm / head): the unit
    a pipeline *stage* executes (launch/pipeline_prefill.py).  Aux losses
    are dropped — stages are inference-path."""
    struct = period_structure(cfg)

    def body(x, period_params):
        for spec, p in zip(struct, period_params):
            x, _ = _position_block(cfg, spec, p, x, pos)
        return x, None

    x, _ = jax.lax.scan(body, x, positions)
    return x


def embed_tokens(cfg: ArchConfig, params: Params, tokens):
    if cfg.embed_inputs:
        return tokens.astype(jnp.dtype(cfg.compute_dtype))  # already (B,S,d)
    return params["embed"][tokens]


def unembed_matrix(cfg: ArchConfig, params: Params):
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def chunked_ce_loss(cfg: ArchConfig, params: Params, h, labels,
                    chunk: int = 512):
    """Mean CE over tokens without materializing (B, S, V) logits."""
    b, s, d = h.shape
    w = unembed_matrix(cfg, params)                     # (V, d)
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    nc = s // chunk
    hc = h.reshape(b, nc, chunk, d).swapaxes(0, 1)      # (nc, B, c, d)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def step(tot, xs):
        hh, ll = xs
        logits = (hh.astype(jnp.float32) @
                  w.astype(jnp.float32).T)              # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc))
    return tot / (b * s)


def lm_loss(cfg: ArchConfig, params: Params, batch) -> Tuple[jax.Array, Dict]:
    """batch: {'tokens' (B,S) or 'embeds' (B,S,d), 'labels' (B,S),
    optional 'positions'}."""
    tokens = batch.get("embeds", batch.get("tokens"))
    b, s = tokens.shape[:2]
    pos = batch.get("positions")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embed_tokens(cfg, params, tokens)
    h, aux = backbone(cfg, params, x, pos)
    ce = chunked_ce_loss(cfg, params, h, batch["labels"])
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------- decode
def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Dict:
    """Decode cache pytree: per period position, leaves stacked over periods."""
    struct = period_structure(cfg)
    np_ = n_periods(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    s = cfg.ssm
    din = (s.expand * cfg.d_model) if s else 0
    entries = []
    for spec in struct:
        if spec["mixer"] == "attn":
            kdt = jnp.int8 if cfg.kv_dtype == "int8" else cdt
            shape = (np_, batch, max_len, cfg.n_kv_heads, cfg.hd)
            # a buffer of its own per leaf: a donated cache may not hand
            # one buffer to two leaves
            e = {"k": jnp.zeros(shape, kdt), "v": jnp.zeros(shape, kdt)}
            if cfg.kv_dtype == "int8":
                e["k_scale"] = jnp.ones(shape[:-1] + (1,), jnp.float32)
                e["v_scale"] = jnp.ones(shape[:-1] + (1,), jnp.float32)
            entries.append(e)
        else:
            entries.append({
                "conv": jnp.zeros((np_, batch, s.conv - 1, din), cdt),
                "ssm": jnp.zeros((np_, batch, din, s.state), jnp.float32),
            })
    return {"layers": entries,
            "length": jnp.zeros((batch,), jnp.int32)}


def decode_step(cfg: ArchConfig, params: Params, cache: Dict, tokens,
                live=None):
    """One token for every sequence.  tokens (B,) int32 (or (B, d) embeds);
    ``live`` (B,) bool marks the rows that attend and that MoE layers
    route (None: all); a row outside it gives logits no one reads.

    Returns (logits (B, V), new_cache, load): ``load`` (n_moe_layers,
    n_held) int32 counts the pairs each held expert took, in layer order.
    Attention positions carry their stacked K/V through the scan over
    periods and write only each slot's new row, so a donated cache is
    updated in place; Mamba states are small and pass through as scanned
    inputs and outputs.
    """
    struct = period_structure(cfg)
    np_ = n_periods(cfg)
    length = cache["length"]
    if cfg.embed_inputs and tokens.ndim == 2:
        x = tokens[:, None].astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = params["embed"][tokens][:, None]            # (B, 1, d)

    new_layers, loads = [], []
    for pos_i, spec in enumerate(struct):
        p_stacked = params["positions"][pos_i]
        c_stacked = cache["layers"][pos_i]
        # the expert weights stay stacked: the kernels read a period's in
        # place, where the scan's slice would copy them whole
        p_xs = {n: v for n, v in p_stacked.items() if n != "moe"}

        if spec["mixer"] == "attn":
            def body(carry, per, spec=spec, p_stacked=p_stacked):
                x, c = carry
                p, i = per
                h = L.apply_norm(cfg, p["norm1"], x)
                y, c = L.attention_decode(cfg, p["attn"], h, c, length,
                                          layer=i, live=live)
                x, load = _serve_ffn(cfg, spec, p, x + y, live, p_stacked, i)
                return (x, c), load

            (x, new_c), load = jax.lax.scan(body, (x, c_stacked),
                                            (p_xs, jnp.arange(np_)))
        else:
            def body(x, per, spec=spec, p_stacked=p_stacked):
                p, c, i = per
                h = L.apply_norm(cfg, p["norm1"], x)
                y, nconv, nssm = L.mamba_decode(
                    cfg, p["mamba"], h, c["conv"], c["ssm"])
                x, load = _serve_ffn(cfg, spec, p, x + y, live, p_stacked, i)
                return x, ({"conv": nconv, "ssm": nssm}, load)

            x, (new_c, load) = jax.lax.scan(
                body, x, (p_xs, c_stacked, jnp.arange(np_)))
        new_layers.append(new_c)
        if load is not None:
            loads.append(load)                          # (P, n_held)

    h = L.apply_norm(cfg, params["final_norm"], x)[:, 0]   # (B, d)
    w = unembed_matrix(cfg, params)
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    load = (jnp.stack(loads, axis=1).reshape(-1, loads[0].shape[-1])
            if loads else _no_load(cfg))
    return logits, {"layers": new_layers, "length": length + 1}, load


def prefill(cfg: ArchConfig, params: Params, tokens, max_len: int,
            live=None):
    """Process a full prompt; return (last_logits (B, V), filled cache,
    load).  ``live`` (B, S) bool marks the rows MoE layers route (None:
    all); ``load`` is as in :func:`decode_step`.  Periods are unrolled,
    each layer handing on its K/V (attention) or (conv, ssm) state."""
    b, s = tokens.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embed_tokens(cfg, params, tokens)
    struct = period_structure(cfg)
    extras: List[List] = [[] for _ in struct]
    loads = []
    for per in range(n_periods(cfg)):
        for pos_i, spec in enumerate(struct):
            stacked = params["positions"][pos_i]
            p = jax.tree.map(lambda a: a[per], stacked)
            x, extra = _mixer(cfg, spec, p, x, pos, kv_out=True)
            x, load = _serve_ffn(cfg, spec, p, x, live, stacked, per)
            extras[pos_i].append(extra)
            if load is not None:
                loads.append(load)
    extras = [jax.tree.map(lambda *xs: jnp.stack(xs), *e) for e in extras]
    h = L.apply_norm(cfg, params["final_norm"], x)

    cdt = jnp.dtype(cfg.compute_dtype)
    entries = []
    for pos_i, spec in enumerate(struct):
        ex = extras[pos_i]
        if spec["mixer"] == "attn":
            k, v = ex                                   # (P, B, S, Hkv, hd)
            pad = [(0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0)]
            if cfg.kv_dtype == "int8":
                k8, ks = L.kv_quantize(k)
                v8, vs = L.kv_quantize(v)
                spad = pad[:-1] + [(0, 0)]
                entries.append({
                    "k": jnp.pad(k8, pad), "v": jnp.pad(v8, pad),
                    "k_scale": jnp.pad(ks, spad, constant_values=1.0),
                    "v_scale": jnp.pad(vs, spad, constant_values=1.0)})
            else:
                entries.append({"k": jnp.pad(k.astype(cdt), pad),
                                "v": jnp.pad(v.astype(cdt), pad)})
        else:
            conv, ssm = ex                              # (P,B,K-1,Din),(P,B,Din,N)
            entries.append({"conv": conv.astype(cdt), "ssm": ssm})
    cache = {"layers": entries,
             "length": jnp.full((b,), s, jnp.int32)}
    w = unembed_matrix(cfg, params)
    logits = h[:, -1].astype(jnp.float32) @ w.astype(jnp.float32).T
    return logits, cache, jnp.stack(loads) if loads else _no_load(cfg)
