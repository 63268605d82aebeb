"""Sharding rules: pytree-path -> PartitionSpec over the production mesh.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  Conventions:

  * batch shards over ("pod","data"); vocab / heads / d_ff / experts /
    mamba-inner over "model";
  * FSDP archs (jamba-398B, qwen3-moe-235B) additionally shard the d_model
    axis of weights over "data" (ZeRO-3 style) so params fit HBM;
  * optimizer moments are ZeRO-1 sharded over "data" for non-FSDP archs;
  * every rule checks divisibility and falls back to replication — GSPMD
    *could* pad, but even sharding keeps per-device memory predictable.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


# ----------------------------------------------------------------- parameters
def _param_rule(cfg: ArchConfig, path: str, shape: Tuple[int, ...],
                mesh: Mesh) -> P:
    m = mesh_axis_size(mesh, "model")
    dsz = mesh_axis_size(mesh, "data")
    fsdp = "data" if cfg.fsdp else None

    def ax(dim: int, name: Optional[str]) -> Optional[str]:
        if name is None:
            return None
        size = m if name == "model" else dsz
        return name if _div(shape[dim], size * 1) else None

    def spec(*names) -> P:
        # Trim/extend to leaf rank; a leading stacked axis gets None.
        extra = len(shape) - len(names)
        names = (None,) * extra + tuple(names)
        return P(*[ax(i, n) for i, n in enumerate(names)])

    leaf = path.split("/")[-1]
    parent = path.split("/")[-2] if "/" in path else ""

    if leaf in ("embed", "lm_head"):
        return spec("model", fsdp)
    if parent in ("attn", "cross"):
        if leaf == "wq" or leaf == "wk" or leaf == "wv":
            return spec(fsdp, "model")
        if leaf == "wo":
            return spec("model", fsdp)
        if leaf in ("bq", "bk", "bv"):
            return spec("model")
        return spec(None)                                # q_norm / k_norm
    if parent in ("mlp", "shared"):
        if leaf in ("gate", "up"):
            return spec(fsdp, "model")
        if leaf == "down":
            return spec("model", fsdp)
    if parent == "moe":
        e = cfg.moe.held[1] if cfg.moe else 0            # experts held
        ep = _div(e, m)                                  # expert parallelism
        if leaf == "router":
            return spec(None, None)
        if leaf in ("w_gate", "w_up"):
            if ep:
                return spec("model", fsdp, None)
            return spec(None, fsdp, "model")
        if leaf == "w_down":
            if ep:
                return spec("model", None, fsdp)
            return spec(None, "model", fsdp)
        if leaf == "shared_gate":
            return spec(None, None)
    if parent == "mamba":
        if leaf == "in_proj":
            return spec(fsdp, "model")
        if leaf == "out_proj":
            return spec("model", fsdp)
        if leaf in ("conv_w", "x_proj", "A_log"):
            return spec("model", None)
        if leaf == "dt_w":
            return spec(None, "model")
        if leaf in ("conv_b", "dt_b", "D"):
            return spec("model")
    # norms, biases, anything else: replicated (stacked axis still None)
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def param_specs(cfg: ArchConfig, params_tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching ``params_tree`` (arrays or SDS)."""
    def rule(path, leaf):
        return _param_rule(cfg, _path_str(path), leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(rule, params_tree)


# ------------------------------------------------------------------ optimizer
def opt_specs(cfg: ArchConfig, pspecs: Any, params_tree: Any,
              mesh: Mesh) -> Any:
    """ZeRO-1: moments take the param spec + shard the first free axis over
    'data'.  FSDP params are already data-sharded; keep their spec."""
    dsz = mesh_axis_size(mesh, "data")

    def rule(spec: P, leaf) -> P:
        if cfg.fsdp:
            return spec
        names = list(spec) + [None] * (len(leaf.shape) - len(spec))
        if "data" in names:
            return P(*names)
        for i, n in enumerate(names):
            if n is None and _div(leaf.shape[i], dsz) and leaf.shape[i] >= dsz:
                names[i] = "data"
                break
        return P(*names)

    return jax.tree.map(rule, pspecs, params_tree,
                        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------------------------- batches
def batch_specs(cfg: ArchConfig, batch_tree: Any, mesh: Mesh) -> Any:
    """Shard the leading batch axis over ("pod","data") when divisible."""
    baxes = batch_axes(mesh)
    bsize = int(np.prod([mesh_axis_size(mesh, a) for a in baxes]))

    def rule(leaf):
        shape = leaf.shape
        if not shape:
            return P()
        first = baxes if _div(shape[0], bsize) else None
        return P(first, *([None] * (len(shape) - 1)))

    return jax.tree.map(rule, batch_tree)


# --------------------------------------------------------------------- caches
def cache_specs(cfg: ArchConfig, cache_tree: Any, mesh: Mesh) -> Any:
    """Decode-cache sharding.

    KV caches (..., B, S, Hkv, hd): batch over ("pod","data") when divisible
    — otherwise (long_500k, B=1) the *sequence* axis shards over "data"
    (sequence-parallel cache).  Hkv over "model" when divisible, else hd.
    """
    m = mesh_axis_size(mesh, "model")
    baxes = batch_axes(mesh)
    bsize = int(np.prod([mesh_axis_size(mesh, a) for a in baxes]))
    dsz = mesh_axis_size(mesh, "data")

    def rule(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        leafname = name.split("/")[-1]
        if leafname == "length":
            return P(baxes if _div(shape[0], bsize) else None)
        if leafname in ("k", "v", "xk", "xv", "k_scale", "v_scale"):
            stacked, b, s, hkv, hd = shape
            bspec = baxes if _div(b, bsize) else None
            sspec = None if bspec else ("data" if _div(s, dsz) else None)
            if _div(hkv, m):
                hspec, dspec = "model", None
            elif _div(hd, m):
                hspec, dspec = None, "model"
            else:
                hspec = dspec = None
            return P(None, bspec, sspec, hspec, dspec)
        if leafname == "conv":                           # (P, B, K-1, Din)
            bspec = baxes if _div(shape[1], bsize) else None
            return P(None, bspec, None,
                     "model" if _div(shape[3], m) else None)
        if leafname == "ssm":                            # (P, B, Din, N)
            bspec = baxes if _div(shape[1], bsize) else None
            return P(None, bspec, "model" if _div(shape[2], m) else None,
                     None)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_tree)
