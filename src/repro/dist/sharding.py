"""Sharding-spec rules for the paper's mesh (§3.1 containment policy).

The mesh axes mirror the cluster: ``model`` is the intra-pod electrical
domain (TP/EP), ``data``/``pod`` carry data parallelism across the OCS core.
Specs are derived *by name and shape*, never by architecture: every init
function in ``repro.models`` uses a small stable vocabulary of leaf names
(``wq``/``wk``/``wv``/``wi``/``wg`` column-parallel, ``wo``/``out_proj``/…
row-parallel, MoE expert stacks), so one rule set covers all 10 registered
architectures.

Divisibility is checked per leaf: a dim that does not divide the axis size
degrades to replicated — a poor layout is acceptable, a compile error is not.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import dp_axes, mesh_axis_sizes

__all__ = [
    "_path_str",
    "batch_specs",
    "cache_specs",
    "mesh_axis_sizes",
    "param_pspec",
    "param_specs",
    "shard_map_dp",
    "to_shardings",
    "zero1_dim",
    "zero1_specs",
]

# weights whose *input* dim is the sharded matmul dim (Megatron row-parallel:
# output projections, low-rank up-projections back to d_model)
_ROW_PARALLEL = frozenset(
    {"wo", "out_proj", "dt_proj", "ts_b", "w_b", "w2"}
)
# MoE expert-stacked weights: the leading expert dim rides the ``model`` axis
# (EP shares the in-pod electrical fabric with TP, configs/common.py)
_EXPERT_STACKED = frozenset({"wi", "wg", "wo"})


def _path_str(path) -> str:
    """tree_flatten_with_path key → 'units/l0/mix/wq' (test vocabulary)."""
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path
    )


def param_pspec(
    key: str, shape: Tuple[int, ...], model: int, is_moe: bool
) -> P:
    """PartitionSpec of one parameter leaf for a ``model``-wide TP axis.

    ``key`` is the '/'-joined tree path; ``shape`` the *global* (possibly
    layer-stacked) shape.  Exactly one dim is sharded: the expert dim for
    MoE expert stacks, the input dim for row-parallel weights, the output
    dim otherwise.  Indivisible candidates degrade to replicated.
    """
    nd = len(shape)
    spec = [None] * nd
    if nd < 2 or model <= 0:
        return P(*spec)
    parts = key.split("/")
    leaf = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""

    def ok(dim: int) -> bool:
        return shape[dim] > 0 and shape[dim] % model == 0

    # MoE expert stacks are 4-D when layer-stacked: (units, E, in, out)
    if is_moe and leaf in _EXPERT_STACKED and parent == "ffn" and nd >= 4:
        if ok(nd - 3):
            spec[nd - 3] = "model"
            return P(*spec)

    if leaf in _ROW_PARALLEL or (leaf == "wv" and parent == "ffn"):
        cand = nd - 2  # rwkv channel-mix wv is (d_ff, d): row-parallel
    else:
        cand = nd - 1
    if ok(cand):
        spec[cand] = "model"
    return P(*spec)


def param_specs(params: Any, mesh, cfg, fsdp: bool = False):
    """Spec tree for a parameter (or same-shaped moment) pytree.

    With ``fsdp`` the ZeRO-3 layout additionally shards each leaf over the
    DP axes on a dim the TP rule left replicated.
    """
    sizes = mesh_axis_sizes(mesh)
    model = sizes.get("model", 1)
    dp = dp_axes(mesh)
    dp_total = 1
    for a in dp:
        dp_total *= sizes[a]
    is_moe = getattr(cfg, "moe", None) is not None

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        key = _path_str(path)
        shape = tuple(leaf.shape)
        base = list(param_pspec(key, shape, model, is_moe))
        if fsdp and dp:
            d = zero1_dim(key, shape, model, dp_total, is_moe)
            if d is not None:
                base[d] = dp if len(dp) > 1 else dp[0]
        specs.append(P(*base))
    return jax.tree_util.tree_unflatten(treedef, specs)


def zero1_dim(
    key: str,
    shape: Tuple[int, ...],
    model: int,
    data: int,
    is_moe: bool,
) -> Optional[int]:
    """Scatter dim for ZeRO-1: the first dim the TP spec leaves replicated
    that divides the DP width.  ``None`` → the leaf stays replicated (the
    optimizer update is redundantly computed, never wrong)."""
    if data <= 0:
        return None
    base = param_pspec(key, shape, model, is_moe)
    padded = list(base) + [None] * (len(shape) - len(base))
    for d, size in enumerate(shape):
        if padded[d] is None and size > 0 and size % data == 0:
            return d
    return None


def zero1_specs(moments: Any, mesh, cfg, use_pod: bool = False):
    """Spec tree for fp32 optimizer moments sharded over DP (ZeRO-1).

    ``use_pod`` additionally spreads the scatter dim over the ``pod`` axis
    (the ZeRO-3/fsdp layout, where the moments are the HBM bottleneck)."""
    sizes = mesh_axis_sizes(mesh)
    model = sizes.get("model", 1)
    axes: Tuple[str, ...] = ("data",) if "data" in sizes else ()
    if use_pod and "pod" in sizes:
        axes = axes + ("pod",)
    total = 1
    for a in axes:
        total *= sizes[a]
    is_moe = getattr(cfg, "moe", None) is not None

    flat, treedef = jax.tree_util.tree_flatten_with_path(moments)
    specs = []
    for path, leaf in flat:
        key = _path_str(path)
        shape = tuple(leaf.shape)
        base = list(param_pspec(key, shape, model, is_moe))
        if axes:
            d = zero1_dim(key, shape, model, total, is_moe)
            if d is not None:
                base[d] = axes if len(axes) > 1 else axes[0]
        specs.append(P(*base))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_specs(batch: Dict[str, Any], mesh):
    """Batch leaves shard dim 0 over the DP axes when divisible."""
    dp = dp_axes(mesh)
    sizes = mesh_axis_sizes(mesh)
    total = 1
    for a in dp:
        total *= sizes[a]

    def spec(leaf) -> P:
        shape = tuple(leaf.shape)
        if (
            dp
            and len(shape) >= 1
            and shape[0] > 0
            and shape[0] % total == 0
        ):
            return P(dp if len(dp) > 1 else dp[0])
        return P()

    return jax.tree_util.tree_map(spec, batch)


def cache_specs(cache: Any, mesh, cfg, seq_shard: bool = False):
    """KV/state cache specs: batch dim over DP, heads (or head_dim) over
    ``model``.  Layer-stacked entries carry a leading units dim, so the
    batch dim is index 1 for rank ≥ 4 leaves and index 0 otherwise.
    ``seq_shard`` (long-context, batch=1 cells) moves the DP sharding to
    the sequence/state dim instead of the batch dim."""
    dp = dp_axes(mesh)
    sizes = mesh_axis_sizes(mesh)
    model = sizes.get("model", 1)
    total = 1
    for a in dp:
        total *= sizes[a]

    def spec(leaf) -> P:
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd == 0:
            return P()
        out = [None] * nd
        bdim = 1 if nd >= 4 else 0
        if seq_shard and bdim + 1 < nd:
            bdim = bdim + 1
        if dp and shape[bdim] > 1 and shape[bdim] % total == 0:
            out[bdim] = dp if len(dp) > 1 else dp[0]
        if model > 1 and nd >= 2:
            for d in (nd - 2, nd - 1):
                if d != bdim and shape[d] > 0 and shape[d] % model == 0:
                    out[d] = "model"
                    break
        return P(*out)

    return jax.tree_util.tree_map(spec, cache)


def to_shardings(spec_tree: Any, mesh):
    """PartitionSpec tree → NamedSharding tree on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_map_dp(f, mesh, in_specs, out_specs, manual_axes: Sequence[str]):
    """shard_map manual over ``manual_axes`` with the rest auto (GSPMD)."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(manual_axes),
        check_vma=False,
    )
