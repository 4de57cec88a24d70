"""Attention variants: GQA/MQA (+softcap, sliding window, bias), MLA
(DeepSeek-V3 latent attention with compressed-cache absorbed decode),
and cross-attention (whisper).

All functions are cache-polymorphic:

* ``cache=None``            — training / scoring over a full sequence
* ``cache=(…), pos=None``   — prefill: full sequence, cache rows [0:S] written
* ``cache=(…), pos=scalar`` — decode: single-token step, cache row pos written

A cache is stacked over the layers of a scan, and the layer owns index
``layer`` of it: it writes only its new rows at ``(layer, 0, start)`` and, in
decode, reads its K/V back from there.  Shapes: x (B, S, d); GQA cache k/v
(L, B, S_max, Hkv, Dh); MLA cache (c_kv (L, B, S_max, R), k_rope
(L, B, S_max, Dr)).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from . import shard_hints
from .layers import apply_rope, dense_init, norm, softcap

BIG_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, kv_pos, window, valid_len=None):
    """Additive fp32 mask: causal + sliding window + cache validity."""
    q = q_pos[:, None]
    k = kv_pos[None, :]
    ok = k <= q
    ok &= k > q - window
    if valid_len is not None:
        ok &= k < valid_len
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _write_rows(buf, rows, layer, start):
    """Write ``rows`` (B, S, ...) into the stacked cache ``buf``
    (L, B, S_max, ...) at layer ``layer``, positions ``start`` onward."""
    idx = (layer, 0, start) + (0,) * (rows.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, rows[None].astype(buf.dtype), idx)


def _read_layer(bufs, layer):
    """Returns ``(bufs, rows)``: the stacked buffers that decode has just
    written its row to, and layer ``layer``'s (B, S_max, ...) part of each.

    The buffers keep the step argument's row-major layout.  Left free, the
    TPU compiler lays the scan's carried cache out for this read, and copies
    the whole cache in and out of the scan on every step."""
    bufs = tuple(with_layout_constraint(b, Layout(tuple(range(b.ndim)))) for b in bufs)
    return bufs, tuple(jax.lax.dynamic_index_in_dim(b, layer, 0, keepdims=False) for b in bufs)


ATTN_Q_CHUNK = 1024  # flash-pattern query blocking for the XLA path


def sdpa_chunked(
    qg, kv_k, kv_v, q_pos, kv_pos, *, scale, window, cap, valid, causal=True,
    chunk=ATTN_Q_CHUNK,
):
    """Exact attention, scanned over query blocks.

    qg: (B, Sq, Hkv, G, Dq); kv_k: (B, Sk, Hkv, Dq); kv_v: (B, Sk, Hkv, Dv).
    Never materializes the full (…, Sq, Sk) score tensor — peak extra memory
    is O(chunk × Sk).  This is the flash-attention access pattern expressed
    in XLA; the Pallas kernel (repro.kernels.flash_attention) is the
    TPU-native version of the same contract.
    """
    B, Sq, hkv, g, dq = qg.shape
    dv = kv_v.shape[-1]
    if Sq <= 2 * chunk or Sq % chunk:
        sc = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, kv_k, preferred_element_type=jnp.float32
        ) * scale
        sc = softcap(sc, cap)
        if causal:
            sc = sc + _mask_bias(q_pos, kv_pos, window, valid)
        pr = jax.nn.softmax(sc, axis=-1).astype(qg.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", pr, kv_v)

    n = Sq // chunk
    qc = jnp.moveaxis(qg.reshape(B, n, chunk, hkv, g, dq), 1, 0)
    pc = jnp.moveaxis(q_pos.reshape(n, chunk), 0, 0)

    @jax.checkpoint
    def block(q_blk, pos_blk):
        sc = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q_blk, kv_k, preferred_element_type=jnp.float32
        ) * scale
        sc = softcap(sc, cap)
        if causal:
            sc = sc + _mask_bias(pos_blk, kv_pos, window, valid)
        pr = jax.nn.softmax(sc, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", pr, kv_v)

    def body(_, xs):
        q_blk, pos_blk = xs
        return None, block(q_blk, pos_blk)

    _, out = jax.lax.scan(body, None, (qc, pc))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, hkv, g, dv)


# ---------------------------------------------------------------------------
# head padding for mesh-divisible sharding (optimized data plane, §Perf)
# ---------------------------------------------------------------------------

import numpy as _np


def _head_pad_plan(hq: int, hkv: int, max_waste: float = 1.26):
    """Pad (hq, hkv) to mesh-divisible counts by replicating kv heads r×
    and permuting q heads into the padded group structure.

    Returns (r, hkv_p, g_p, hq_p, perm, inv) or None when heads already
    divide the model axis / padding would waste > ``max_waste`` compute.
    ``perm[slot] = original q head or -1 (zero pad)``; ``inv`` maps
    original head -> padded slot.  Exactness: padded slots are sliced away
    before the output projection (tested against the unpadded path).
    """
    m = shard_hints.model_size()
    if m <= 1 or (hq % m == 0 and hkv % m == 0):
        return None
    r = m // math.gcd(hkv, m)
    hkv_p = hkv * r
    if hkv_p % m:
        return None
    g = hq // hkv
    g_p = -(-hq // hkv_p)
    hq_p = g_p * hkv_p
    if hq_p > hq * max_waste or g > r * g_p:
        return None
    perm = _np.full(hq_p, -1, dtype=_np.int64)
    for j in range(hkv):
        for t in range(g):
            c, p = divmod(t, g_p)
            perm[(j * r + c) * g_p + p] = j * g + t
    inv = _np.zeros(hq, dtype=_np.int64)
    for s, o in enumerate(perm):
        if o >= 0:
            inv[o] = s
    return r, hkv_p, g_p, hq_p, jnp.asarray(perm), jnp.asarray(inv)


# ---------------------------------------------------------------------------
# GQA / MQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, cfg.pdtype),
        "wk": dense_init(ks[1], d, hkv * hd, cfg.pdtype),
        "wv": dense_init(ks[2], d, hkv * hd, cfg.pdtype),
        "wo": dense_init(ks[3], hq * hd, d, cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.pdtype)
    return p


def gqa_attention(
    params: dict,
    x: jnp.ndarray,
    cfg,
    *,
    window=None,
    causal: bool = True,
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    layer: Optional[jnp.ndarray] = None,
    pos: Optional[jnp.ndarray] = None,
):
    """Returns (y, new_cache).  ``window``: None→cfg/sliding default handling
    is done by the caller (pass an int or traced scalar).  ``cache`` is the
    stacked (k, v) of which this layer owns index ``layer``."""
    B, S, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    w = BIG_WINDOW if window is None else window

    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = shard_hints.hint_bshd(q.reshape(B, S, hq, hd))
    k = shard_hints.hint_bshd(k.reshape(B, S, hkv, hd))
    v = shard_hints.hint_bshd(v.reshape(B, S, hkv, hd))

    if cache is None or pos is None:  # train / prefill: positions 0..S-1
        q_pos = jnp.arange(S)
    else:  # decode
        q_pos = jnp.asarray(pos)[None]
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)

    new_cache = None
    kv_k, kv_v = k, v  # train / prefill: attend over the fresh rows
    kv_pos = jnp.arange(S)
    valid = None
    if cache is not None:
        start = 0 if pos is None else jnp.asarray(pos)
        with jax.named_scope("kv_cache"):
            new_cache = (
                _write_rows(cache[0], k, layer, start),
                _write_rows(cache[1], v, layer, start),
            )
        if pos is not None:  # decode: attend over this layer's whole cache
            new_cache, (kv_k, kv_v) = _read_layer(new_cache, layer)
            kv_k, kv_v = kv_k.astype(x.dtype), kv_v.astype(x.dtype)
            kv_pos = jnp.arange(kv_k.shape[1])
            valid = start + 1

    scale = 1.0 / math.sqrt(hd)
    pad = (
        _head_pad_plan(hq, hkv)
        if (shard_hints.active() and pos is None)
        else None
    )
    if pad is not None:
        # optimized path: pad heads to mesh-divisible counts (§Perf iter 2)
        r, hkv_p, g_p, hq_p, perm, inv = pad
        qp = jnp.take(q, jnp.maximum(perm, 0), axis=2)
        qp = qp * (perm >= 0).astype(qp.dtype)[None, None, :, None]
        kp = shard_hints.hint_bshd(jnp.repeat(kv_k, r, axis=2))
        vp = shard_hints.hint_bshd(jnp.repeat(kv_v, r, axis=2))
        qp = shard_hints.hint_bshd(qp)
        out = sdpa_chunked(
            qp.reshape(B, S, hkv_p, g_p, hd), kp, vp, q_pos, kv_pos,
            scale=scale, window=w, cap=cfg.attn_softcap, valid=valid,
            causal=causal,
        )
        out = shard_hints.hint_bshd(out.reshape(B, S, hq_p, hd))
        out = jnp.take(out, inv, axis=2)  # drop pad slots, restore order
    else:
        qg = q.reshape(B, S, hkv, g, hd)
        out = sdpa_chunked(
            qg, kv_k, kv_v, q_pos, kv_pos,
            scale=scale, window=w, cap=cfg.attn_softcap, valid=valid,
            causal=causal,
        )
        out = shard_hints.hint_bshd(out.reshape(B, S, hq, hd))
    out = out.reshape(B, S, hq * hd)
    return out @ params["wo"].astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wdq": dense_init(ks[0], d, m.q_lora_rank, cfg.pdtype),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), cfg.pdtype)},
        "wuq": dense_init(ks[1], m.q_lora_rank, h * qk, cfg.pdtype),
        "wdkv": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, cfg.pdtype),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), cfg.pdtype)},
        "wuk": dense_init(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim, cfg.pdtype),
        "wuv": dense_init(ks[4], m.kv_lora_rank, h * m.v_head_dim, cfg.pdtype),
        "wo": dense_init(ks[5], h * m.v_head_dim, d, cfg.pdtype),
    }


def mla_attention(
    params: dict,
    x: jnp.ndarray,
    cfg,
    *,
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    layer: Optional[jnp.ndarray] = None,
    pos: Optional[jnp.ndarray] = None,
):
    """MLA.  Train/prefill uses the expanded form; decode uses the absorbed
    form over the compressed cache (c_kv, k_rope) — the MLA memory win.
    ``cache`` is the stacked (c_kv, k_rope) of which this layer owns index
    ``layer``."""
    m = cfg.mla
    B, S, d = x.shape
    h = cfg.num_heads
    nope, rdim, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = 1.0 / math.sqrt(nope + rdim)

    cq = x @ params["wdq"].astype(x.dtype)
    cq = norm(params["q_norm"], cq, "rmsnorm")
    qfull = (cq @ params["wuq"].astype(x.dtype)).reshape(B, S, h, nope + rdim)
    q_nope, q_rope = qfull[..., :nope], qfull[..., nope:]

    dkv = x @ params["wdkv"].astype(x.dtype)
    c_kv, k_rope = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    c_kv = norm(params["kv_norm"], c_kv, "rmsnorm")

    if cache is None or pos is None:
        q_pos = jnp.arange(S)
    else:
        q_pos = jnp.asarray(pos)[None]
    q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)
    k_rope = apply_rope(k_rope[..., None, :], q_pos, cfg.rope_theta)[..., 0, :]

    new_cache = None
    if cache is not None:
        start = 0 if pos is None else jnp.asarray(pos)
        with jax.named_scope("kv_cache"):
            new_cache = (
                _write_rows(cache[0], c_kv, layer, start),
                _write_rows(cache[1], k_rope, layer, start),
            )

        if pos is not None:  # decode over compressed cache (absorbed)
            new_cache, (cc, cr) = _read_layer(new_cache, layer)
            S_max = cc.shape[1]
            wuk = params["wuk"].astype(x.dtype).reshape(m.kv_lora_rank, h, nope)
            # absorb W_uk into q:  (B,1,h,nope)·(r,h,nope) -> (B,1,h,r)
            q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, wuk)
            sc = jnp.einsum(
                "bqhr,bkr->bhqk", q_abs, cc.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            sc = sc + jnp.einsum(
                "bqhr,bkr->bhqk", q_rope, cr.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            sc = sc * scale
            kv_pos = jnp.arange(S_max)
            sc = sc + _mask_bias(q_pos, kv_pos, BIG_WINDOW, jnp.asarray(pos) + 1)
            pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
            out_c = jnp.einsum("bhqk,bkr->bqhr", pr, cc.astype(x.dtype))
            wuv = params["wuv"].astype(x.dtype).reshape(m.kv_lora_rank, h, vdim)
            out = jnp.einsum("bqhr,rhv->bqhv", out_c, wuv)
            out = out.reshape(B, S, h * vdim)
            return out @ params["wo"].astype(x.dtype), new_cache

    # expanded path (train / prefill), chunked over query blocks
    k_nope = (c_kv @ params["wuk"].astype(x.dtype)).reshape(B, S, h, nope)
    v = shard_hints.hint_bshd(
        (c_kv @ params["wuv"].astype(x.dtype)).reshape(B, S, h, vdim)
    )
    kq = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,h,nope+rdim)
    kk = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, h, rdim))], axis=-1
    )
    kq = shard_hints.hint_bshd(kq)
    kk = shard_hints.hint_bshd(kk)
    kv_pos = jnp.arange(S)
    out = sdpa_chunked(
        kq[:, :, :, None, :], kk, v, q_pos, kv_pos,
        scale=scale, window=BIG_WINDOW, cap=None, valid=None, causal=True,
    )
    out = shard_hints.hint_bshd(out.reshape(B, S, h, vdim))
    out = out.reshape(B, S, h * vdim)
    return out @ params["wo"].astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder -> encoder output)
# ---------------------------------------------------------------------------

def init_cross(key, cfg) -> dict:
    d, hq, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, hq * hd, cfg.pdtype),
        "wk": dense_init(ks[1], d, hq * hd, cfg.pdtype),
        "wv": dense_init(ks[2], d, hq * hd, cfg.pdtype),
        "wo": dense_init(ks[3], hq * hd, d, cfg.pdtype),
    }


def cross_attention(params: dict, x: jnp.ndarray, enc: jnp.ndarray, cfg):
    B, S, d = x.shape
    Se = enc.shape[1]
    hq, hd = cfg.num_heads, cfg.head_dim
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, hq, hd)
    k = (enc @ params["wk"].astype(x.dtype)).reshape(B, Se, hq, hd)
    v = (enc @ params["wv"].astype(x.dtype)).reshape(B, Se, hq, hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    sc = sc / math.sqrt(hd)
    pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, S, hq * hd)
    return out @ params["wo"].astype(x.dtype)
