"""Parameter and state sharding rules (Megatron §5.1, GSPMD-style).

``param_logical(path, leaf)`` maps every parameter to logical axes by its
name; a Strategy's rules table then yields PartitionSpecs. The rules encode
the paper's §5.1 scheme exactly:

  * MLP:  A (w_gate/w_up) split over COLUMNS (d_ff), B (w_down) over ROWS
    (d_ff)  =>  GeLU local, ONE forward all-reduce (validated by
    tests/test_tp_collectives.py against the lowered HLO).
  * Attention: wq/wk/wv column-split by head, wo row-split.
  * Embedding / LM head: vocab-split (Megatron vocab-parallel).
  * MoE: expert axis split (expert parallelism) — the survey's MoE-era
    all-to-all pattern; or TP-in-expert when Strategy.expert_parallel=False.
  * Mamba2: in_proj column-split (whole SSD heads per device, local scan),
    out_proj row-split — the paper's insight transferred to SSM blocks
    (DESIGN.md §4.1).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.pspec import logical_to_spec
from repro.core.strategy import Strategy

# leaf name -> logical axes of the TRAILING dims (leading dims — layer
# stacking from scan — are unsharded).
_TRAILING = {
    # attention (column for qkv, row for o)
    "wq": ("d_model", "heads"),
    "wk": ("d_model", "kv_heads"),
    "wv": ("d_model", "kv_heads"),
    "wo": ("heads", "d_model"),
    # dense MLP (column, column, row)
    "w_gate": ("d_model", "d_ff"),
    "w_up": ("d_model", "d_ff"),
    "w_down": ("d_ff", "d_model"),
    # embeddings (vocab-parallel)
    "embed": ("vocab", "d_model"),
    "tok_embed": ("vocab", "d_model"),
    "lm_head": ("d_model", "vocab"),
    # MoE
    "router": ("d_model", None),
    # Mamba2
    "in_proj": ("d_model", "ssm_inner"),
    "out_proj": ("ssm_inner", "d_model"),
    "conv_w": (None, "ssm_inner"),
    "conv_b": ("ssm_inner",),
    "dt_bias": ("ssm_heads",),
    "A_log": ("ssm_heads",),
    "D": ("ssm_heads",),
    "gn_scale": ("ssm_inner",),
}

# MoE expert tensors, keyed by (parent, leaf)
_MOE_TRAILING = {
    "w_gate": ("experts", "d_model", "d_ff_moe"),
    "w_up": ("experts", "d_model", "d_ff_moe"),
    "w_down": ("experts", "d_ff_moe", "d_model"),
}

_REPLICATED_NAMES = {"ln1", "ln2", "lnx", "norm", "final_norm", "enc_norm",
                     "q_norm", "k_norm", "gate_attn", "gate_mlp"}


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        else:
            out.append(str(k))
    return tuple(out)


def param_logical(path, leaf) -> Tuple[Optional[str], ...]:
    """Logical axes for one parameter leaf (padded with None on the left
    for scan-stacked leading dims)."""
    names = _path_names(path)
    leaf_name = names[-1]
    if leaf_name in _REPLICATED_NAMES:
        return (None,) * leaf.ndim
    if leaf_name in _MOE_TRAILING and "moe" in names:
        trailing = _MOE_TRAILING[leaf_name]
    elif leaf_name in _TRAILING:
        trailing = _TRAILING[leaf_name]
    else:
        return (None,) * leaf.ndim
    pad = leaf.ndim - len(trailing)
    assert pad >= 0, (names, leaf.shape, trailing)
    return (None,) * pad + tuple(trailing)


def _divisible(shape, spec: P, mesh: Mesh) -> P:
    """Drop shardings that don't divide (GSPMD pads, but for PARAMETERS we
    prefer exact shardings; activations stay padded-sharded)."""
    new = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            new.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh.shape[a]
        new.append(ax if dim % size == 0 else None)
    return P(*new)


def param_pspecs(params: Any, strategy: Strategy, mesh: Mesh):
    """Pytree of PartitionSpec matching ``params``."""
    rules = strategy.rules(mesh)

    def one(path, leaf):
        spec = logical_to_spec(param_logical(path, leaf), rules)
        spec = _divisible(leaf.shape, spec, mesh)
        if strategy.fsdp:
            # ZeRO-3/FSDP: additionally shard over "data" on the first free
            # divisible dim; GSPMD inserts the per-use all-gather.
            spec = zero1_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params: Any, strategy: Strategy, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params, strategy, mesh))


def zero1_spec(spec: P, shape, mesh: Mesh, axis: str = "data") -> P:
    """ZeRO-1: additionally shard an optimizer-state tensor over ``axis``
    on the first unsharded, divisible dim (DeepSpeed-style, used by
    MT-NLG [29])."""
    if axis not in mesh.axis_names:
        return spec
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    used = set()
    for ax in entries:
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            if a is not None:
                used.add(a)
    if axis in used:            # already sharded over it (e.g. FSDP+ZeRO-1)
        return P(*entries)
    for i, (dim, ax) in enumerate(zip(shape, entries)):
        if ax is None and dim % mesh.shape[axis] == 0:
            entries[i] = axis
            return P(*entries)
    return spec


def _state_leaf_spec(state_leaf, param_leaf, spec: P, mesh: Mesh,
                     zero1: bool) -> P:
    """Spec for an optimizer-state leaf derived from its parameter's spec.
    Handles full-shape (m/v/master), row-factored (vr = shape[:-1]) and
    col-factored (vc = shape[:-2] + shape[-1:]) Adafactor states."""
    pshape, sshape = param_leaf.shape, state_leaf.shape
    entries = tuple(spec) + (None,) * (len(pshape) - len(spec))
    if sshape == pshape:
        out = P(*entries)
    elif len(pshape) >= 2 and sshape == pshape[:-1]:
        out = P(*entries[:-1])
    elif len(pshape) >= 2 and sshape == pshape[:-2] + pshape[-1:]:
        out = P(*(entries[:-2] + entries[-1:]))
    elif sshape == ():
        return P()
    else:
        out = P(*([None] * len(sshape)))
    if zero1:
        out = zero1_spec(out, sshape, mesh)
    return _divisible(sshape, out, mesh)


def opt_state_pspecs(opt_state, params, strategy: Strategy, mesh: Mesh):
    """Specs matching the optimizer-state pytree (AdamW m/v/master or
    Adafactor vr/vc), ZeRO-1-sharded over "data" when enabled."""
    pspecs = param_pspecs(params, strategy, mesh)
    out = {}
    for k, sub in opt_state.items():
        if k == "step":
            out[k] = P()
        else:
            out[k] = jax.tree.map(
                lambda s, p, sp: _state_leaf_spec(s, p, sp, mesh,
                                                  strategy.zero1),
                sub, params, pspecs)
    return out


# ---------------------------------------------------------------- caches

def cache_pspecs(cache: Any, strategy: Strategy, mesh: Mesh, batch: int):
    """KV / SSM cache specs: batch over data (when divisible), heads over
    model. Cache layouts: kv k/v (L,B,W,Hkv,D); ssm state (L,B,H,P,N);
    conv (L,B,W,C); xkv like kv.

    A cache carrying a ``"ptab"`` page table (the serve engine's paged
    layout) holds its decoder KV as one flat POOL
    (L, n_pages, Hkv, page_size, D) shared by every slot instead of
    per-slot rows: the pool is head-sharded over "model" (each device
    keeps Hkv/tp heads of EVERY page — intra-operator TP for serving)
    and never batch-sharded (pages have no batch dim; data-parallel
    serving replicates whole engines, serve/parallel.py). The page table
    itself and any dense leaves riding along (the enc-dec cross-KV
    ``xkv``, SSM states) keep their usual specs."""
    rules = strategy.rules(mesh)
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    bspec = rules["batch"] if batch % dp == 0 else None

    model_size = mesh.shape.get("model", 1)
    paged = isinstance(cache, dict) and "ptab" in cache

    def one(path, leaf):
        names = _path_names(path)
        if leaf.ndim == 0 or names[-1] == "pos":
            return P()
        if paged and names[0] == "kv" and names[-1] in ("k", "v"):
            # the flat page pool: shard the kv-head axis over "model"
            # (fall back to replicated when GQA heads don't divide — the
            # page axis must stay whole, a gather index crosses it)
            spec = P(None, None,
                     rules["kv_heads"] if leaf.shape[2] % model_size == 0
                     else None, None, None)
            return _divisible(leaf.shape, spec, mesh)
        if names[-1] in ("k", "v"):
            # Prefer KV-head sharding (Megatron); when GQA kv_heads don't
            # divide the model axis, shard the cache SEQUENCE dim instead
            # (context-parallel decode) so the cache still fits.
            if leaf.shape[3] % model_size == 0:
                spec = P(None, bspec, None, rules["kv_heads"], None)
            elif leaf.shape[2] % model_size == 0:
                spec = P(None, bspec, "model", None, None)
            else:
                spec = P(None, bspec, None, None, None)
        elif names[-1] == "state":
            spec = P(None, bspec, rules["ssm_heads"], None, None)
        elif names[-1] == "conv":
            spec = P(None, bspec, None, rules["ssm_inner"])
        else:
            spec = P(*([None] * leaf.ndim))
        return _divisible(leaf.shape, spec, mesh)

    return jax.tree_util.tree_map_with_path(one, cache)
