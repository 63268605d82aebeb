"""ShapeDtypeStruct stand-ins + shardings for every (arch x shape x step).

``make_cell(arch, shape, mesh)`` returns weak-type-correct, shardable SDS
trees for the step function being lowered — no device allocation ever
happens, so a production mesh of host devices can compile a full-size cell.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as sh
from repro.configs.base import ArchConfig, ShapeSpec, SHAPES, get_arch
from repro.models import Model, build_model
from repro.optim import adamw_init
from repro.train import TrainState, make_train_step

SDS = jax.ShapeDtypeStruct


class CellSpec(NamedTuple):
    """Everything needed to lower one (arch x shape) cell."""
    fn: Any                  # callable to jit
    args: Tuple              # SDS trees
    in_shardings: Tuple      # NamedSharding trees
    out_shardings: Any
    donate: Tuple[int, ...]
    kind: str


def _sds_tree(tree) -> Any:
    return jax.tree.map(lambda l: SDS(l.shape, l.dtype), tree)


def params_sds(model: Model) -> Any:
    return jax.eval_shape(lambda: model.init(jax.random.key(0)))


def _named_tree(mesh: Mesh, specs) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sds(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, SDS]:
    b, s = shape.global_batch, shape.seq_len
    cdt = jnp.dtype(cfg.compute_dtype)
    batch: Dict[str, SDS] = {}
    if cfg.embed_inputs:
        batch["embeds"] = SDS((b, s, cfg.d_model), cdt)
        if cfg.is_encdec:
            batch["tokens"] = SDS((b, s), jnp.int32)
    else:
        batch["tokens"] = SDS((b, s), jnp.int32)
    batch["labels"] = SDS((b, s), jnp.int32)
    return batch


def make_cell(arch: str, shape_name: str, mesh: Mesh) -> CellSpec:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    psds = params_sds(model)
    pspecs = sh.param_specs(cfg, psds, mesh)
    psh = _named_tree(mesh, pspecs)

    if shape.kind == "train":
        osds = jax.eval_shape(lambda p: adamw_init(p, cfg.adam_dtype), psds)
        mspecs = sh.opt_specs(cfg, pspecs, psds, mesh)   # params-shaped tree
        msh = _named_tree(mesh, mspecs)
        osh = type(osds)(mu=msh, nu=msh,
                         count=NamedSharding(mesh, P()))
        state_sds = TrainState(psds, osds, SDS((), jnp.int32))
        state_sh = TrainState(psh, osh, NamedSharding(mesh, P()))
        bsds = batch_sds(cfg, shape)
        bsh = _named_tree(mesh, sh.batch_specs(cfg, bsds, mesh))
        if cfg.grad_accum > 1 or cfg.grad_compression != "none":
            from repro.distributed import CompressionSpec
            from repro.distributed.overlap import make_accum_train_step
            comp = (CompressionSpec(kind=cfg.grad_compression)
                    if cfg.grad_compression != "none" else None)
            fn = make_accum_train_step(model,
                                       n_micro=max(cfg.grad_accum, 1),
                                       compression=comp)
        else:
            fn = make_train_step(model)
        rep = NamedSharding(mesh, P())
        out_sh = (state_sh, jax.tree.map(lambda _: rep, {
            "loss": 0, "lr": 0, "ce": 0, "aux": 0, "grad_norm": 0}))
        return CellSpec(fn, (state_sds, bsds), (state_sh, bsh), out_sh,
                        (0,), "train")

    if shape.kind == "prefill":
        bsds = batch_sds(cfg, shape)
        bsds.pop("labels")
        bsh = _named_tree(mesh, sh.batch_specs(cfg, bsds, mesh))
        fn = lambda p, b: model.prefill(p, b, shape.seq_len)
        csds = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                     shape.seq_len))
        csh = _named_tree(mesh, sh.cache_specs(cfg, csds, mesh))
        logit_sh = NamedSharding(
            mesh, P(sh.batch_axes(mesh)
                    if shape.global_batch % _bsz(mesh) == 0 else None,
                    "model" if cfg.vocab_size % mesh.shape["model"] == 0
                    else None))
        return CellSpec(fn, (psds, bsds), (psh, bsh), (logit_sh, csh),
                        (), "prefill")

    # decode: one new token against a seq_len-deep cache
    b = shape.global_batch
    csds = jax.eval_shape(
        lambda: model.init_cache(b, shape.seq_len, shape.seq_len))
    csh = _named_tree(mesh, sh.cache_specs(cfg, csds, mesh))
    if cfg.embed_inputs and not cfg.is_encdec:
        tsds = SDS((b, cfg.d_model), jnp.dtype(cfg.compute_dtype))
    else:
        tsds = SDS((b,), jnp.int32)
    tsh = _named_tree(mesh, sh.batch_specs(cfg, tsds, mesh))
    fn = lambda p, c, t: model.decode_step(p, c, t)
    logit_sh = NamedSharding(
        mesh, P(sh.batch_axes(mesh) if b % _bsz(mesh) == 0 else None,
                "model" if cfg.vocab_size % mesh.shape["model"] == 0
                else None))
    return CellSpec(fn, (psds, csds, tsds), (psh, csh, tsh),
                    (logit_sh, csh), (1,), "decode")


def _bsz(mesh: Mesh) -> int:
    out = 1
    for a in sh.batch_axes(mesh):
        out *= mesh.shape[a]
    return out


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), D = tokens/step."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens                  # forward only
    return 2.0 * n * shape.global_batch          # decode: 1 token/seq
