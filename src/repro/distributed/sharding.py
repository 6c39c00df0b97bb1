"""Divisibility-aware auto-sharding rules for params, optimizer state,
activations, and decode caches on the production mesh.

Layout summary (DESIGN.md; exercised by launch/dryrun.py):

  * weights (2D+): last dim -> "model" when divisible, a leading non-layer
    dim -> "data" when divisible (FSDP x TP hybrid). Stacked-layer leading
    axes (scanned) are never sharded. Fallback = replicate the offending
    dim — correctness over cleverness; the roofline table shows the cost.
  * batch/token inputs: batch -> ("pod","data") on the multi-pod mesh.
  * decode KV caches (L,B,C,K,D): batch -> data axes, cache seq -> "model".
    KV-head counts (1..40) rarely divide the model axis, sequence always
    does; softmax/contraction over the sharded seq dim lowers to
    all-reduces, which GSPMD handles.
  * recurrent states (SSM / RG-LRU): batch -> data, width -> "model" when
    divisible; states are small.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _norm_axes(axes):
    """Collapse 1-tuples to the bare axis name so specs compare canonically
    (P(..., "data", ...) rather than P(..., ("data",), ...))."""
    if not axes:
        return None
    if isinstance(axes, tuple) and len(axes) == 1:
        return axes[0]
    return axes


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _div(dim: int, mesh: Mesh, axes) -> bool:
    return dim % axis_size(mesh, axes) == 0


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

PROFILES = ("baseline", "serve_model_only", "expert_parallel", "pure_dp")


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               mesh: Mesh, stacked_layers: bool,
               profile: str = "baseline") -> P:
    """Spec for one parameter tensor. ``stacked_layers``: leading dim is the
    scanned layer axis (never sharded).

    Profiles (§Perf hillclimb; EXPERIMENTS.md):
      baseline         — FSDP x TP hybrid: last dim -> model, an earlier dim
                         -> data. Memory-optimal, but serving pays a weight
                         all-gather over `data` every step.
      serve_model_only — weights sharded over `model` only, replicated over
                         data: zero weight collectives at decode (weights
                         must fit HBM/16 per chip).
      expert_parallel  — MoE expert stacks (L,E,d,f): E -> model (classic
                         expert parallelism; dispatch becomes an all-to-all
                         of token activations instead of weight gathers);
                         non-expert weights follow serve_model_only... with
                         baseline fallback when E doesn't divide.
      pure_dp          — everything replicated (tiny models: grads all-reduce
                         once instead of per-layer gathers).
    """
    nd = len(shape)
    start = 1 if stacked_layers and nd >= 2 else 0
    dims = list(range(start, nd))
    spec: list = [None] * nd
    if not dims:
        return P()
    if profile == "pure_dp":
        return P(*spec)
    is_expert = "experts" in path
    if profile == "expert_parallel" and is_expert and nd - start == 3:
        e_dim, d_dim = dims[0], dims[1]
        if _div(shape[e_dim], mesh, "model"):
            spec[e_dim] = "model"
            if _div(shape[d_dim], mesh, "data") and \
                    shape[d_dim] >= axis_size(mesh, "data"):
                spec[d_dim] = "data"
            return P(*spec)
        # fall through to baseline rules if E is indivisible
    last = dims[-1]
    if _div(shape[last], mesh, "model") and shape[last] >= axis_size(mesh, "model"):
        spec[last] = "model"
    if profile in ("serve_model_only", "expert_parallel"):
        return P(*spec)
    for d in dims[:-1]:
        if spec[d] is None and _div(shape[d], mesh, "data") and \
                shape[d] >= axis_size(mesh, "data") and shape[d] > 8:
            spec[d] = "data"
            break
    # 1D / leftover: try model on last if unassigned, else replicate
    if spec[last] is None and nd - start == 1 and \
            _div(shape[last], mesh, "model") and \
            shape[last] >= 4 * axis_size(mesh, "model"):
        spec[last] = "model"
    return P(*spec)


def params_shardings(params_shape, mesh: Mesh, profile: str = "baseline"):
    """Tree of NamedShardings matching an eval_shape'd params tree."""
    def one(path, leaf):
        keys = tuple(_seg(p) for p in path)
        stacked = "layers" in keys
        return NamedSharding(mesh, param_spec(keys, leaf.shape, mesh, stacked,
                                              profile))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def _seg(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# --------------------------------------------------------------------------
# batch / cache specs
# --------------------------------------------------------------------------

def batch_shardings(batch_shape, mesh: Mesh, profile: str = "baseline"):
    # pure_dp: batch spreads over EVERY mesh axis (the model axis carries no
    # weights, so it becomes extra data parallelism)
    dp = tuple(mesh.axis_names) if profile == "pure_dp" else data_axes(mesh)

    def one(path, leaf):
        nd = len(leaf.shape)
        if leaf.shape and _div(leaf.shape[0], mesh, dp):
            return NamedSharding(mesh, P(dp, *([None] * (nd - 1))))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, batch_shape)


def cache_shardings(cache_shape, mesh: Mesh, arch_type: str):
    """Decode-state tree sharding, explicit per family:

      dense/moe/vlm:  k/v (L,B,C,K,D), scales (L,B,C,K,1)
                        -> (None, data, model@C, None, None)
      ssm:            conv (L,B,W-1,Cdim) -> (None, data, None, model@Cdim)
                      ssm  (L,B,H,P,N)    -> (None, data, None, None, model@N)
      hybrid:         h (B,w) -> (data, model@w); conv (B,3,w) -> (data,None,model@w)
                      k/v (B,cap,K,D) -> (data, model@cap, None, None)
    """
    dp = data_axes(mesh)

    def mdl(dim: int, min_per_shard: int = 1) -> Optional[str]:
        m = axis_size(mesh, "model")
        return "model" if dim % m == 0 and dim >= m * min_per_shard else None

    def one(path, leaf):
        keys = tuple(_seg(p) for p in path)
        shape = leaf.shape
        name = keys[-1] if keys else ""
        if arch_type in ("dense", "moe", "vlm"):
            bspec = _norm_axes(dp) if _div(shape[1], mesh, dp) else None
            return NamedSharding(mesh, P(None, bspec, mdl(shape[2]), None, None))
        if arch_type == "ssm":
            bspec = _norm_axes(dp) if _div(shape[1], mesh, dp) else None
            if name == "conv":
                return NamedSharding(mesh, P(None, bspec, None, mdl(shape[3])))
            return NamedSharding(mesh, P(None, bspec, None, None, mdl(shape[4])))
        if arch_type == "hybrid":
            bspec = _norm_axes(dp) if _div(shape[0], mesh, dp) else None
            if name == "h":
                return NamedSharding(mesh, P(bspec, mdl(shape[1])))
            if name == "conv":
                return NamedSharding(mesh, P(bspec, None, mdl(shape[2])))
            return NamedSharding(mesh, P(bspec, mdl(shape[1]), None, None))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# degraded (shard-loss) specs — FailSafe-style serving on surviving shards
# --------------------------------------------------------------------------
# A shard-granularity fault removes one slice of the "model" axis. Instead
# of killing the instance, the serving layer re-lays every tensor over the
# SURVIVING model-axis size: specs are recomputed against a mesh whose
# model axis shrank, and the existing divisibility rules do the rest — a
# dim the smaller axis no longer divides falls back to replication
# (correctness over cleverness, same policy as the full mesh).

def abstract_mesh(shape: Tuple[int, ...], names: Tuple[str, ...]):
    """Shape-only mesh (no devices needed): axis sizes ``shape``, axis
    names ``names``."""
    return jax.sharding.AbstractMesh(shape, names)


def degraded_mesh(mesh: Mesh, lost_shards) -> Mesh:
    """The surviving mesh: ``mesh`` with its model axis shrunk by the lost
    shard count. Raises if every shard is lost — that is instance death,
    not degradation (the engine escalates before calling this)."""
    lost = len(set(lost_shards))
    sizes, names = [], []
    for name in mesh.axis_names:
        size = int(mesh.shape[name])
        if name == "model":
            size -= lost
            if size < 1:
                raise ValueError(
                    f"all {mesh.shape[name]} model shards lost — no "
                    "surviving slice to degrade onto")
        names.append(name)
        sizes.append(size)
    return abstract_mesh(tuple(sizes), tuple(names))


def degraded_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
                  mesh: Mesh, lost_shards, stacked_layers: bool,
                  profile: str = "baseline") -> P:
    """``param_spec`` re-evaluated over the surviving model-axis slice."""
    return param_spec(path, shape, degraded_mesh(mesh, lost_shards),
                      stacked_layers, profile)


def degraded_params_shardings(params_shape, mesh: Mesh, lost_shards,
                              profile: str = "baseline"):
    return params_shardings(params_shape, degraded_mesh(mesh, lost_shards),
                            profile)


def degraded_cache_shardings(cache_shape, mesh: Mesh, lost_shards,
                             arch_type: str):
    return cache_shardings(cache_shape, degraded_mesh(mesh, lost_shards),
                           arch_type)


def _spec_uses_model(spec: P) -> bool:
    for axes in spec:
        if axes == "model" or (isinstance(axes, tuple) and "model" in axes):
            return True
    return False


def degradation_summary(params_shape, mesh: Mesh, lost_shards,
                        profile: str = "serve_model_only",
                        cache_shape=None, arch_type: str = "") -> dict:
    """What degrading onto the surviving slice costs, as data: how many
    param/cache tensors stay model-sharded vs fall back to replication
    (the smaller axis broke their divisibility), and the per-shard byte
    growth that implies. The engine computes this once per degrade and
    surfaces it through ``/health`` as ``degradation.layout``."""
    surviving = degraded_mesh(mesh, lost_shards)
    n_model = int(mesh.shape["model"])
    n_left = int(surviving.shape["model"])

    def census(tree_shape, shardings_fn, *args):
        full = shardings_fn(tree_shape, mesh, *args)
        deg = shardings_fn(tree_shape, surviving, *args)
        kept = dropped = 0
        bytes_full = bytes_deg = 0
        leaves = zip(jax.tree_util.tree_leaves(tree_shape),
                     jax.tree_util.tree_leaves(full),
                     jax.tree_util.tree_leaves(deg))
        for leaf, fsh, dsh in leaves:
            nbytes = int(np.prod(leaf.shape)) * jnp_itemsize(leaf.dtype)
            was = _spec_uses_model(fsh.spec)
            now = _spec_uses_model(dsh.spec)
            if now:
                kept += 1
            elif was:
                dropped += 1
            # per-shard residency: bytes / product of axis sizes the spec
            # actually shards over
            bytes_full += nbytes // max(_shard_ways(fsh.spec, mesh), 1)
            bytes_deg += nbytes // max(_shard_ways(dsh.spec, surviving), 1)
        return kept, dropped, bytes_full, bytes_deg

    pk, pd, pbf, pbd = census(params_shape, params_shardings, profile)
    out = {
        "n_shards": n_model, "surviving": n_left,
        "lost_shards": sorted(set(lost_shards)),
        "capacity_frac": n_left / n_model,
        "params_model_sharded": pk,
        "params_replicate_fallback": pd,
        "param_bytes_per_shard_full": pbf,
        "param_bytes_per_shard_degraded": pbd,
    }
    if cache_shape is not None and arch_type:
        ck, cd, cbf, cbd = census(cache_shape, cache_shardings, arch_type)
        out.update({
            "kv_model_sharded": ck, "kv_replicate_fallback": cd,
            "kv_bytes_per_shard_full": cbf,
            "kv_bytes_per_shard_degraded": cbd,
        })
    return out


def _shard_ways(spec: P, mesh: Mesh) -> int:
    ways = 1
    for axes in spec:
        if axes is None:
            continue
        ways *= axis_size(mesh, axes)
    return ways


def jnp_itemsize(dtype) -> int:
    return int(np.dtype(jax.numpy.dtype(dtype)).itemsize)
