"""Optional activation-sharding hints (the 'optimized' data plane).

GSPMD left alone makes poor choices inside scanned attention blocks — the
dry-run baseline shows fp32 score tensors being all-reduced over the model
axis thousands of times (EXPERIMENTS.md §Perf).  The standard fix (MaxText
et al.) is explicit ``with_sharding_constraint`` on the attention
activations.  This module keeps the models mesh-agnostic: hints are
no-ops until a launcher registers a mesh via :func:`use_hints`.

Baseline (paper-faithful) lowering keeps hints OFF; the optimized
configuration turns them on — the delta is the measured §Perf iteration.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None
_SIZES: dict = {}
_DP: Tuple[str, ...] = ()


def use_hints(mesh: Optional[Mesh]) -> None:
    """Register (or clear, with None) the mesh for activation hints."""
    global _MESH, _SIZES, _DP
    _MESH = mesh
    if mesh is None:
        _SIZES, _DP = {}, ()
    else:
        _SIZES = dict(zip(mesh.axis_names, mesh.devices.shape))
        _DP = tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def active() -> bool:
    return _MESH is not None


def model_size() -> int:
    return _SIZES.get("model", 1)


def _set_sizes_for_test(sizes: dict) -> None:
    """Test hook: drive the head-padding planner without a real mesh
    (``_MESH`` stays None so constraints remain no-ops)."""
    global _SIZES
    _SIZES = dict(sizes)


def _dp_total() -> int:
    n = 1
    for a in _DP:
        n *= _SIZES[a]
    return n


def _apply(x, spec_list):
    """Apply a constraint, dropping axes that are Manual in the current
    tracing context (inside shard_map over the DP axes only the model
    axis remains Auto)."""
    am = jax.sharding.get_abstract_mesh()
    manual = {
        name
        for name, ty in zip(am.axis_names, am.axis_types)
        if ty == AxisType.Manual
    }

    def keep(a):
        if a is None:
            return None
        if isinstance(a, (tuple, list)):
            kept = tuple(x_ for x_ in a if x_ not in manual)
            return kept if kept else None
        return None if a in manual else a

    spec = P(*[keep(a) for a in spec_list])
    if all(a is None for a in spec):
        return x
    if manual:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))


def hint_bshd(x):
    """(B, S, H, D) attention activations: batch over DP, heads over model
    when divisible (else head_dim), sequence replicated."""
    if _MESH is None or x.ndim != 4:
        return x
    B, S, H, D = x.shape
    model = _SIZES.get("model", 1)
    spec = [None, None, None, None]
    if B % _dp_total() == 0 and B > 1:
        spec[0] = _DP
    if H % model == 0:
        spec[2] = "model"
    elif D % model == 0:
        spec[3] = "model"
    return _apply(x, spec)


def hint_bsd(x):
    """(B, S, d) residual-stream activations: batch over DP only."""
    if _MESH is None or x.ndim != 3:
        return x
    B = x.shape[0]
    spec = [None, None, None]
    if B % _dp_total() == 0 and B > 1:
        spec[0] = _DP
    return _apply(x, spec)


def hint_expert(x):
    """(E, C, d) MoE dispatch buffers: experts over model when divisible."""
    if _MESH is None or x.ndim != 3:
        return x
    E = x.shape[0]
    model = _SIZES.get("model", 1)
    spec = [None, None, None]
    if E % model == 0:
        spec[0] = "model"
    elif x.shape[2] % model == 0:
        spec[2] = "model"
    return _apply(x, spec)
