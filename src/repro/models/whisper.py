"""Whisper-style encoder-decoder (audio backbone, conv frontend STUBBED).

Per the assignment, the modality frontend is a stub: inputs are precomputed
frame embeddings (B, S_enc, d_model) — what whisper's two conv layers would
emit.  The transformer backbone (12L enc + 12L dec, layernorm, absolute
positions, cross-attention) is implemented fully.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import cross_attention, gqa_attention, init_cross, init_gqa
from .layers import (
    cross_entropy,
    cross_entropy_fused,
    dense_init,
    init_mlp,
    init_norm,
    mlp,
    norm,
)
from .transformer import _remat_wrap, scan_cached


def _sinusoid(seq: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / dim)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _init_enc_layer(key, cfg) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": init_norm(cfg),
        "attn": init_gqa(ks[0], cfg),
        "ln2": init_norm(cfg),
        "ffn": init_mlp(ks[1], cfg),
    }


def _init_dec_layer(key, cfg) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "ln1": init_norm(cfg),
        "attn": init_gqa(ks[0], cfg),
        "lnx": init_norm(cfg),
        "xattn": init_cross(ks[1], cfg),
        "ln2": init_norm(cfg),
        "ffn": init_mlp(ks[2], cfg),
    }


def init_whisper(key, cfg, max_target_positions: int = 448) -> dict:
    ks = jax.random.split(key, 4)
    enc_keys = jax.random.split(ks[0], cfg.encoder_layers)
    dec_keys = jax.random.split(ks[1], cfg.num_layers)
    return {
        "enc_layers": jax.vmap(lambda k: _init_enc_layer(k, cfg))(enc_keys),
        "enc_ln": init_norm(cfg),
        "tok": (jax.random.normal(ks[2], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(
            cfg.pdtype
        ),
        "pos": (
            jax.random.normal(ks[3], (max_target_positions, cfg.d_model)) * 0.02
        ).astype(cfg.pdtype),
        "dec_layers": jax.vmap(lambda k: _init_dec_layer(k, cfg))(dec_keys),
        "dec_ln": init_norm(cfg),
    }


def encode(params: dict, frames: jnp.ndarray, cfg) -> jnp.ndarray:
    """frames: (B, S_enc, d) precomputed conv-frontend output (stub)."""
    x = frames.astype(cfg.cdtype) + _sinusoid(frames.shape[1], cfg.d_model).astype(
        cfg.cdtype
    )

    def step(x, p):
        h = norm(p["ln1"], x, cfg.norm_kind)
        y, _ = gqa_attention(p["attn"], h, cfg, causal=False)
        x = x + y
        h = norm(p["ln2"], x, cfg.norm_kind)
        return x + mlp(p["ffn"], h, cfg.mlp_kind), None

    x, _ = jax.lax.scan(_remat_wrap(step, cfg), x, params["enc_layers"])
    return norm(params["enc_ln"], x, cfg.norm_kind)


def decode(
    params: dict,
    tokens: jnp.ndarray,
    enc_out: jnp.ndarray,
    cfg,
    cache: Optional[dict] = None,
    mode: str = "train",
    return_hidden: bool = False,
    last_only: bool = False,
):
    """Returns (logits, new_cache).  cache: {"pos", "kv": stacked (k, v)}."""
    B, S = tokens.shape
    decode_mode = mode == "decode"
    pos = cache["pos"] if decode_mode else None
    x = params["tok"].astype(cfg.cdtype)[tokens]
    if decode_mode:
        pe = jax.lax.dynamic_slice_in_dim(params["pos"], cache["pos"], 1, axis=0)
    else:
        pe = params["pos"][:S]
    x = x + pe.astype(cfg.cdtype)[None]

    def step(x, p, c=None, i=None):
        h = norm(p["ln1"], x, cfg.norm_kind)
        y, nc = gqa_attention(p["attn"], h, cfg, cache=c, layer=i, pos=pos)
        x = x + y
        h = norm(p["lnx"], x, cfg.norm_kind)
        x = x + cross_attention(p["xattn"], h, enc_out, cfg)
        h = norm(p["ln2"], x, cfg.norm_kind)
        x = x + mlp(p["ffn"], h, cfg.mlp_kind)
        return x, nc

    if cache is not None:
        x, nkv = scan_cached(step, x, params["dec_layers"], cache["kv"])
        new_cache = {"pos": cache["pos"] + (1 if decode_mode else S), "kv": nkv}
    else:
        def step_nc(x, p):
            return step(x, p)[0], None

        x, _ = jax.lax.scan(_remat_wrap(step_nc, cfg), x, params["dec_layers"])
        new_cache = None
    x = norm(params["dec_ln"], x, cfg.norm_kind)
    if return_hidden:
        return x, new_cache
    if last_only:
        x = x[:, -1:, :]
    logits = (x @ params["tok"].astype(x.dtype).T).astype(jnp.float32)
    return logits, new_cache


def init_whisper_cache(cfg, batch: int, s_max: int):
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (cfg.num_layers, batch, s_max, hkv, hd)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "kv": (jnp.zeros(shape, cfg.cdtype), jnp.zeros(shape, cfg.cdtype)),
    }


def whisper_loss(params, batch, cfg):
    """batch: {"frames": (B,Se,d), "tokens": (B,S), "targets": (B,S)}."""
    enc = encode(params, batch["frames"], cfg)
    h, _ = decode(params, batch["tokens"], enc, cfg, return_hidden=True)
    return cross_entropy_fused(
        h, {"tok": params["tok"]}, batch["targets"], cfg, batch.get("mask")
    )
