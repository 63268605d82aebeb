"""Pipelined prefill over the pod axis — the paper's execution model on the
multi-pod mesh.

The CM accelerator runs inference as a *layer pipeline*: every core holds
its layers' weights permanently and a compiled LCU state machine advances
each core as its input dependencies are satisfied (paper §2/§3).  Here:

  * "core"       -> one pod (16x16 slice of the 2x16x16 mesh)
  * "layer"      -> a stage of n_layers/n_stages layers, weights resident
  * "LCU automaton" -> ``core.pipeline.derive_schedule`` — the Appendix-A
    ``S`` relation evaluated at compile time over ``pointwise`` edges
    (microbatch t of stage s+1 depends on microbatch t of stage s)
  * "SRAM write at cycle+1" -> ``lax.ppermute`` hop per tick

Execution: ``shard_map`` manual over "pod", auto over ("data","model") so
each stage's interior still uses the full 256-chip GSPMD layout.

What this buys (the paper's motivation): per-pod resident weight bytes
divided by n_stages — the multi-pod machine can hold a model n_stages x
larger with inter-pod traffic bounded by one activation hop per microbatch
per tick, at pipeline utilization n_micro / (n_micro + n_stages - 1).
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as sh
from repro.configs.base import ArchConfig
from repro.core import pipeline
from repro.models import lm


def stage_config(cfg: ArchConfig, n_stages: int) -> ArchConfig:
    assert cfg.n_layers % n_stages == 0
    return dataclasses.replace(cfg, n_layers=cfg.n_layers // n_stages)


def init_stage_params_sds(cfg: ArchConfig, n_stages: int):
    """SDS tree: per-stage period stacks stacked again on a stage axis."""
    scfg = stage_config(cfg, n_stages)

    def one():
        full = lm.init_lm(scfg, jax.random.key(0))
        return full["positions"]

    stage = jax.eval_shape(one)
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_stages,) + l.shape, l.dtype),
        stage)


def head_params_sds(cfg: ArchConfig):
    def one():
        full = lm.init_lm(stage_config(cfg, 1), jax.random.key(0))
        return {k: v for k, v in full.items() if k != "positions"}
    return jax.eval_shape(one)


def make_pipelined_prefill(cfg: ArchConfig, mesh: Mesh, n_micro: int,
                           seq_len: int, batch: int):
    """Returns (fn, args_sds, in_shardings).  fn(stage_params, head, tokens)
    -> last-token hidden (n_micro, b_m, d)."""
    n_stages = mesh.shape["pod"]
    scfg = stage_config(cfg, n_stages)
    b_m = batch // n_micro
    # the paper's dependency automaton -> static schedule
    sched = pipeline.derive_schedule(["pointwise"] * (n_stages - 1), n_micro)
    table = jnp.asarray(sched.table)                 # (S, T)
    n_ticks = sched.n_ticks

    def body(stage_params_local, embed_local, tokens_all):
        pme = jax.tree.map(lambda l: l[0], stage_params_local)
        sid = jax.lax.axis_index("pod")
        pos = jnp.broadcast_to(jnp.arange(seq_len)[None], (b_m, seq_len))
        buf = jnp.zeros((b_m, seq_len, cfg.d_model),
                        jnp.dtype(cfg.compute_dtype))
        outs = jnp.zeros((n_micro, b_m, cfg.d_model),
                         jnp.dtype(cfg.compute_dtype))
        # pod is manual here, data/model stay auto: pin the activation's
        # batch dim to data so the partitioner never replicates it there
        act_spec = (P("data", None, None)
                    if b_m % mesh.shape["data"] == 0 else P(None, None, None))

        def tick(carry, tck):
            buf, outs = carry
            item = table[sid, tck]                   # -1 => idle
            safe = jnp.clip(item, 0, n_micro - 1)
            toks = jax.lax.dynamic_index_in_dim(
                tokens_all, safe, axis=0, keepdims=False)  # (b_m, S)
            x0 = embed_local[0][toks]                # stage-0 input
            x_in = jnp.where(sid == 0, x0, buf)
            x_in = jax.lax.with_sharding_constraint(x_in, act_spec)
            y = lm.run_stack(scfg, pme, x_in, pos)
            y = jnp.where(item >= 0, y, buf)         # idle: hold
            outs = jnp.where((sid == n_stages - 1) & (item >= 0),
                             outs.at[safe].set(y[:, -1, :]), outs)
            buf = jax.lax.ppermute(
                y, "pod",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            buf = jax.lax.with_sharding_constraint(buf, act_spec)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast the final answer to all stages; f32 sidesteps an XLA-CPU
        # AllReducePromotion crash on bf16 all-reduce (copy-opcode clone bug)
        outs = jax.lax.psum(outs.astype(jnp.float32), "pod")
        return outs.astype(jnp.dtype(cfg.compute_dtype))

    stage_sds = init_stage_params_sds(cfg, n_stages)
    tokens_sds = jax.ShapeDtypeStruct((n_micro, b_m, seq_len), jnp.int32)

    # shardings: stage axis -> pod; interior -> the standard model rules
    scfg_rules = stage_config(cfg, n_stages)
    inner = sh.param_specs(scfg_rules,
                           jax.eval_shape(
                               lambda: lm.init_lm(scfg_rules,
                                                  jax.random.key(0))),
                           mesh)["positions"]
    stage_specs = jax.tree.map(lambda s: P(*(("pod",) + tuple(s))), inner,
                               is_leaf=lambda x: isinstance(x, P))
    embed_spec = P(None, "model", None)              # (1, V, d) stacked below
    tokens_spec = P(None, "data", None)

    def fn(stage_params, embed, tokens):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pod"), stage_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
                      P(None), P(None)),
            out_specs=P(None),
            axis_names={"pod"},              # manual over pod; data/model auto
            check_vma=False)(stage_params, embed, tokens)

    embed_sds = jax.ShapeDtypeStruct(
        (1, cfg.vocab_size, cfg.d_model), jnp.dtype(cfg.param_dtype))
    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), stage_specs,
                          is_leaf=lambda x: isinstance(x, P)),
             NamedSharding(mesh, embed_spec),
             NamedSharding(mesh, tokens_spec))
    return fn, (stage_sds, embed_sds, tokens_sds), in_sh, sched


def pipeline_param_init(cfg: ArchConfig, n_stages: int, shardings):
    """A jitted ``key -> (stage_params, embed)`` that draws the parameters
    directly into ``shardings`` (the first two entries of
    ``make_pipelined_prefill``'s in_shardings): each device materialises
    only its own stage, so a model no single device can hold is never
    gathered onto one.  Stage ``s`` is ``init_lm(stage_config(cfg,
    n_stages), split(key)[s])["positions"]``."""
    scfg = stage_config(cfg, n_stages)

    def init(key):
        ks = jax.random.split(key, n_stages + 1)
        stages = jax.vmap(lambda k: lm.init_lm(scfg, k)["positions"])(
            ks[:n_stages])
        embed = (jax.random.normal(ks[-1], (cfg.vocab_size, cfg.d_model))
                 * 0.02).astype(jnp.dtype(cfg.param_dtype))
        return stages, embed[None]

    return jax.jit(init, out_shardings=tuple(shardings[:2]))
