"""Decoder-only LM assembly: layer plans, scan-over-layers, KV caches.

A model is a *prologue* stack (e.g. DeepSeek's leading dense layers) plus a
scan over homogeneous *repeat units* (1 layer for dense models; 8 for jamba's
attn:mamba 1:7 interleave; 2 for gemma2's local/global alternation).  Scanning
the unit keeps the compiled HLO to one unit body regardless of depth — this
is what makes the 61-layer DeepSeek dry-run compile in seconds.

Caches mirror the layer plan: each unit element owns a cache entry stacked
over units; ``init_cache`` builds the pytree, prefill writes it, decode
updates it.  The stacked cache rides in the layer scan's carry
(``scan_cached``) and each layer writes only what it changes at its own
index, so a step whose cache is donated updates it in place.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import BIG_WINDOW, gqa_attention, init_gqa, init_mla, mla_attention
from .layers import (
    cross_entropy,
    cross_entropy_fused,
    embed,
    init_embed,
    init_mlp,
    init_norm,
    mlp,
    norm,
    unembed,
)
from .moe import init_moe, moe_mlp
from .rwkv import (
    init_rwkv_channel,
    init_rwkv_time,
    rwkv_channel_mix,
    rwkv_state_shapes,
    rwkv_time_mix,
)
from .ssm import init_mamba, mamba_block, mamba_state_shape


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str  # attn | mamba | rwkv
    moe: bool = False
    window: Optional[int] = None  # sliding window (gemma2 local layers)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    prologue: Tuple[LayerSpec, ...]
    unit: Tuple[LayerSpec, ...]
    n_units: int


def layer_plan(cfg) -> LayerPlan:
    moe = cfg.moe
    first_dense = moe.first_dense if moe else 0

    def ffn_is_moe(global_idx: int) -> bool:
        if moe is None or global_idx < first_dense:
            return False
        return (global_idx % moe.every) == (moe.every - 1) if moe.every > 1 else True

    if cfg.block_pattern:
        pattern = cfg.block_pattern
        if cfg.num_layers % len(pattern):
            raise ValueError("num_layers must be a multiple of the block pattern")
        if moe and len(pattern) % moe.every:
            raise ValueError("pattern length must be a multiple of moe.every")
        unit = tuple(
            LayerSpec(kind=k, moe=ffn_is_moe(i)) for i, k in enumerate(pattern)
        )
        return LayerPlan((), unit, cfg.num_layers // len(pattern))
    if cfg.local_global:
        if cfg.num_layers % 2:
            raise ValueError("local_global needs even num_layers")
        unit = (
            LayerSpec("attn", window=cfg.sliding_window),
            LayerSpec("attn", window=None),
        )
        return LayerPlan((), unit, cfg.num_layers // 2)
    prologue = tuple(LayerSpec("attn", moe=False) for _ in range(first_dense))
    unit = (LayerSpec("attn", moe=moe is not None),)
    return LayerPlan(prologue, unit, cfg.num_layers - first_dense)


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(key, spec: LayerSpec, cfg) -> dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": init_norm(cfg), "ln2": init_norm(cfg)}
    if spec.kind == "attn":
        p["mix"] = init_mla(ks[0], cfg) if cfg.attn_kind == "mla" else init_gqa(ks[0], cfg)
    elif spec.kind == "mamba":
        p["mix"] = init_mamba(ks[0], cfg)
    elif spec.kind == "rwkv":
        p["mix"] = init_rwkv_time(ks[0], cfg)
    else:
        raise ValueError(spec.kind)
    if spec.kind == "rwkv":
        p["ffn"] = init_rwkv_channel(ks[1], cfg)
    elif spec.moe:
        p["ffn"] = init_moe(ks[1], cfg)
    else:
        # prologue dense layers in MoE models use the dense d_ff
        p["ffn"] = init_mlp(ks[1], cfg)
    return p


def _cache_shapes(spec: LayerSpec, cfg, batch: int, s_max: int):
    """Shape/dtype tree of one layer's cache entry."""
    dt = cfg.cdtype
    if spec.kind == "attn":
        if cfg.attn_kind == "mla":
            m = cfg.mla
            return (
                ((batch, s_max, m.kv_lora_rank), dt),
                ((batch, s_max, m.qk_rope_head_dim), dt),
            )
        return (
            ((batch, s_max, cfg.num_kv_heads, cfg.head_dim), dt),
            ((batch, s_max, cfg.num_kv_heads, cfg.head_dim), dt),
        )
    if spec.kind == "mamba":
        s1, s2 = mamba_state_shape(cfg, batch)
        return ((s1, dt), (s2, jnp.float32))
    if spec.kind == "rwkv":
        s1, s2, s3 = rwkv_state_shapes(cfg, batch)
        return ((s1, dt), (s2, jnp.float32), (s3, dt))
    raise ValueError(spec.kind)


def _layer_state(cache, layer):
    """Layer ``layer``'s entry of a stacked recurrent state."""
    return tuple(jax.lax.dynamic_index_in_dim(b, layer, 0, keepdims=False) for b in cache)


def _put_layer_state(cache, state, layer):
    """Replace layer ``layer``'s entry of a stacked recurrent state."""
    with jax.named_scope("kv_cache"):
        return tuple(
            jax.lax.dynamic_update_index_in_dim(b, s.astype(b.dtype), layer, 0)
            for b, s in zip(cache, state)
        )


def _apply_layer(spec: LayerSpec, p, x, cfg, cache, layer, pos, scan_chunk_size):
    """One layer.  Each sub-block runs under a ``jax.named_scope`` (``norm``,
    ``attn``/``mix``, ``mlp``/``moe``) that lands in the compiled ops'
    ``op_name`` metadata, so a profiler trace can attribute device time to
    it; the residual add belongs to its sub-block.

    ``cache`` is this layer's entry stacked over the layers of its scan, of
    which it owns index ``layer`` (None in training).  Returns the stacked
    entry with this layer's part updated: attention writes its new K/V rows
    (the stack grows with context), mamba and RWKV replace their whole
    fixed-size state."""
    aux = jnp.zeros((), jnp.float32)
    state = _layer_state(cache, layer) if cache is not None and spec.kind != "attn" else None
    with jax.named_scope("norm"):
        h = norm(p["ln1"], x, cfg.norm_kind)
    if spec.kind == "attn":
        window = spec.window if spec.window else BIG_WINDOW
        with jax.named_scope("attn"):
            if cfg.attn_kind == "mla":
                y, new_mix = mla_attention(
                    p["mix"], h, cfg, cache=cache, layer=layer, pos=pos
                )
            else:
                y, new_mix = gqa_attention(
                    p["mix"], h, cfg, window=window, cache=cache, layer=layer, pos=pos
                )
            x = x + y
    elif spec.kind == "mamba":
        mix_state = state[:2] if state is not None else None
        with jax.named_scope("mix"):
            y, new_mix = mamba_block(p["mix"], h, cfg, state=mix_state, chunk=scan_chunk_size)
            x = x + y
    elif spec.kind == "rwkv":
        tstate = state[:2] if state is not None else None
        with jax.named_scope("mix"):
            y, new_mix = rwkv_time_mix(p["mix"], h, cfg, state=tstate, chunk=scan_chunk_size)
            x = x + y
    else:
        raise ValueError(spec.kind)
    with jax.named_scope("norm"):
        h = norm(p["ln2"], x, cfg.norm_kind)
    if spec.kind == "rwkv":
        cstate = state[2] if state is not None else None
        with jax.named_scope("mlp"):
            y, new_c = rwkv_channel_mix(p["ffn"], h, cfg, state=cstate)
            x = x + y
        new_mix = new_mix + (new_c,)
    elif spec.moe:
        with jax.named_scope("moe"):
            y, aux = moe_mlp(p["ffn"], h, cfg)
            x = x + y
    else:
        with jax.named_scope("mlp"):
            x = x + mlp(p["ffn"], h, cfg.mlp_kind)
    if state is not None:
        new_mix = _put_layer_state(cache, new_mix, layer)
    return x, new_mix, aux


# ---------------------------------------------------------------------------
# whole-model init / apply
# ---------------------------------------------------------------------------

def init_lm(key, cfg) -> dict:
    plan = layer_plan(cfg)
    k_embed, k_pro, k_units = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": init_embed(k_embed, cfg),
        "final_norm": init_norm(cfg),
    }
    if plan.prologue:
        keys = jax.random.split(k_pro, len(plan.prologue))
        params["pro"] = jax.vmap(lambda k: _init_layer(k, plan.prologue[0], cfg))(keys)
    if plan.n_units:
        keys = jax.random.split(k_units, plan.n_units)

        def init_unit(k):
            uks = jax.random.split(k, len(plan.unit))
            return {
                f"l{i}": _init_layer(uks[i], s, cfg) for i, s in enumerate(plan.unit)
            }

        params["units"] = jax.vmap(init_unit)(keys)
    return params


def init_cache(cfg, batch: int, s_max: int):
    """Zero-filled cache pytree matching the layer plan."""
    plan = layer_plan(cfg)

    def entry(spec):
        return tuple(
            jnp.zeros(shape, dtype) for shape, dtype in _cache_shapes(spec, cfg, batch, s_max)
        )

    def stacked_entry(spec, n):
        return tuple(
            jnp.zeros((n,) + shape, dtype)
            for shape, dtype in _cache_shapes(spec, cfg, batch, s_max)
        )

    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if plan.prologue:
        cache["pro"] = stacked_entry(plan.prologue[0], len(plan.prologue))
    if plan.n_units:
        cache["units"] = {
            f"l{i}": stacked_entry(s, plan.n_units) for i, s in enumerate(plan.unit)
        }
    return cache


def _remat_wrap(fn, cfg):
    if cfg.remat_policy == "nothing":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(cfg.remat_policy)


def scan_cached(step, carry, params, cache):
    """Scan ``step(carry, p, cache, i) -> (carry, cache)`` over the layers
    stacked on the leading axis of ``params``, with the stacked ``cache`` in
    the scan's carry beside ``carry``; ``i`` is the layer's index.

    Each layer writes only what it changes into ``cache`` at its index, so
    the cache is neither sliced out of the scan's ``xs`` nor stacked anew
    into its ``ys``.  When the jitted step donates the cache, XLA aliases it
    to the returned cache and updates it in place.  Returns (carry, cache).
    """
    def body(c, p):
        carry, cache, i = c
        carry, cache = step(carry, p, cache, i)
        return (carry, cache, i + 1), None

    (carry, cache, _), _ = jax.lax.scan(
        body, (carry, cache, jnp.zeros((), jnp.int32)), params
    )
    return carry, cache


def apply_lm(
    params: dict,
    tokens: Optional[jnp.ndarray],
    cfg,
    cache: Optional[dict] = None,
    mode: str = "train",  # train | prefill | decode
    inputs_embeds: Optional[jnp.ndarray] = None,
    scan_chunk_size: int = 64,
    return_hidden: bool = False,
    last_only: bool = False,
):
    """Returns (logits fp32 (B,S,V), aux_loss, new_cache).

    * mode="train":   cache ignored (None)
    * mode="prefill": cache required; writes positions [0:S], pos := S
    * mode="decode":  cache required; tokens (B,1), updates at cache["pos"]
    """
    if mode == "train":
        cache = None
    elif cache is None:
        raise ValueError(f"mode={mode!r} requires a cache")
    plan = layer_plan(cfg)
    if inputs_embeds is not None:
        x = inputs_embeds
    else:
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens, cfg)
    decode = mode == "decode"
    pos = cache["pos"] if decode else None

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    if plan.prologue:
        spec = plan.prologue[0]

        def pro_step(carry, p, c=None, i=None):
            x, aux = carry
            x, nc, a = _apply_layer(spec, p, x, cfg, c, i, pos, scan_chunk_size)
            return (x, aux + a), nc

        with jax.named_scope("layers"):
            if cache is not None:
                (x, aux_total), new_cache["pro"] = scan_cached(
                    pro_step, (x, aux_total), params["pro"], cache["pro"]
                )
            else:
                def pro_step_nc(carry, p):
                    return pro_step(carry, p)[0], None

                pro_step_nc = _remat_wrap(pro_step_nc, cfg)
                (x, aux_total), _ = jax.lax.scan(pro_step_nc, (x, aux_total), params["pro"])

    if plan.n_units:
        def unit_step(carry, p, c=None, i=None):
            x, aux = carry
            ncs = {}
            for j, s in enumerate(plan.unit):
                x, ncs[f"l{j}"], a = _apply_layer(
                    s, p[f"l{j}"], x, cfg, c[f"l{j}"] if c is not None else None,
                    i, pos, scan_chunk_size,
                )
                aux = aux + a
            return (x, aux), ncs

        with jax.named_scope("layers"):
            if cache is not None:
                (x, aux_total), new_cache["units"] = scan_cached(
                    unit_step, (x, aux_total), params["units"], cache["units"]
                )
            else:
                def unit_step_nc(carry, p):
                    return unit_step(carry, p)[0], None

                unit_step_nc = _remat_wrap(unit_step_nc, cfg)
                (x, aux_total), _ = jax.lax.scan(unit_step_nc, (x, aux_total), params["units"])

    with jax.named_scope("head"):
        x = norm(params["final_norm"], x, cfg.norm_kind)
    if cache is not None:
        new_cache["pos"] = cache["pos"] + (1 if decode else x.shape[1])
    if return_hidden:
        return x, aux_total, (new_cache if cache is not None else None)
    with jax.named_scope("head"):
        if last_only:
            x = x[:, -1:, :]
        logits = unembed(params["embed"], x, cfg)
    return logits, aux_total, (new_cache if cache is not None else None)


def lm_loss(params, batch, cfg, scan_chunk_size: int = 64):
    """batch: {"tokens": (B,S), "targets": (B,S), optional "mask"}."""
    h, aux, _ = apply_lm(
        params, batch["tokens"], cfg, scan_chunk_size=scan_chunk_size,
        return_hidden=True,
    )
    with jax.named_scope("loss"):
        loss = cross_entropy_fused(
            h, params["embed"], batch["targets"], cfg, batch.get("mask")
        )
    if cfg.moe is not None:
        loss = loss + 0.01 * aux
    return loss
