"""Pallas TPU chunked WKV6 recurrence (RWKV-6 "Finch" time mixing).

The recurrence (per head, state S ∈ ℝ^{K×V}):

    y_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

TPU adaptation: instead of a step-by-step scan (serial, VPU-bound), the
sequence is split into chunks of C tokens; within a chunk everything is
expressed as MXU matmuls + O(C²·K) masked elementwise decay work, and
the (K, V) state is carried across chunks in VMEM scratch (grid's last
dimension is sequential on TPU, so scratch persists across chunk steps).

Numerical stability: all decay ratios are computed as ``exp(Σ log w)`` where
the exponent is a *sum of non-positive terms* (w ∈ (0,1)), so nothing can
overflow — no divisions by decayed-away cumulative products.  Inputs carry
``log_w`` directly (the model computes ``log w = -exp(w_lora)``).

Chunk math (cl = cumsum(log_w) within the chunk, cl_prev = cl shifted):

    inter_t  = (r_t ⊙ exp(cl_prev_t)) · S_in                (C,K)·(K,V) MXU
    A[t,j]   = Σ_k r_t[k] k_j[k] exp(cl_prev_t[k]−cl_j[k])  (j<t)
             = r_t·(u ⊙ k_t)                                (j=t)
    y        = inter + A · v                                (C,C)·(C,V) MXU
    S_out    = diag(exp(cl_C)) S_in + (k ⊙ exp(cl_C−cl))ᵀ · v

A is built one query row at a time (C passes of O(C·K) on the VPU), since
the chip's kernel compiler has no (C, C, K) broadcast, and cumsum is a
lower-triangular matmul, since it has no cumsum either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(
    r_ref, k_ref, v_ref, lw_ref,  # (1, 1, C, K) VMEM windows
    u_ref,  # (1, 1, K)
    s0_ref,  # (1, 1, K, V)
    y_ref,  # (1, 1, C, V)
    sout_ref,  # (1, 1, K, V)
    state_scr,  # VMEM (K, V) fp32
    *,
    chunk: int,
    num_chunks: int,
):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (C, V)
    lw = lw_ref[0, 0].astype(jnp.float32)  # (C, K), all ≤ 0
    u = u_ref[0].astype(jnp.float32)  # (1, K)
    S = state_scr[...]  # (K, V)

    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum): cl[t] = Σ_{i≤t} log w_i
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (col <= row).astype(jnp.float32)
    cl = jax.lax.dot_general(
        tril, lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (C, K)
    cl_prev = cl - lw  # exclusive cumsum: Σ_{i<t} log w_i

    # inter-chunk contribution: y_t += (r_t ⊙ W_{t-1}) · S_in
    r_decay = r * jnp.exp(cl_prev)  # (C, K)
    inter = jax.lax.dot_general(
        r_decay, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, V)

    # intra-chunk attention, built transposed one query row t at a time
    # (Mosaic has no (C, C, K) broadcast): At[j, t] = A[t, j], exponent ≤ 0
    At = jnp.where(row == col, jnp.sum(r * u * k, axis=-1, keepdims=True), 0.0)
    for t in range(1, chunk):
        decay = jnp.exp(jnp.minimum(cl_prev[t : t + 1] - cl, 0.0))  # (C, K)
        a_t = jnp.sum(r[t : t + 1] * k * decay, axis=-1, keepdims=True)  # (C, 1)
        At = At + jnp.where((col == t) & (row < t), a_t, 0.0)
    intra = jax.lax.dot_general(
        At, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0, 0] = (inter + intra).astype(y_ref.dtype)

    # state update: S_out = diag(exp(cl_C)) S_in + (k ⊙ exp(cl_C − cl))ᵀ · v
    total = cl[chunk - 1 :]  # (1, K)
    total_col = jax.lax.dot_general(  # the same sums as a (K, 1) column
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    k_decay = k * jnp.exp(total - cl)  # (C, K), exponent ≤ 0
    S_new = jnp.exp(total_col) * S + jax.lax.dot_general(
        k_decay, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = S_new

    @pl.when(it == num_chunks - 1)
    def _emit_state():
        sout_ref[0, 0] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jnp.ndarray,  # (B, H, T, K)
    k: jnp.ndarray,  # (B, H, T, K)
    v: jnp.ndarray,  # (B, H, T, V)
    log_w: jnp.ndarray,  # (B, H, T, K), entries < 0
    u: jnp.ndarray,  # (H, K)
    s0: jnp.ndarray,  # (B, H, K, V)
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    """Chunked WKV6.  Returns (y (B,H,T,V), s_final (B,H,K,V))."""
    B, H, T, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    pad = (-T) % C
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
        r, k, v = zeros(r), zeros(k), zeros(v)
        log_w = jnp.pad(log_w, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    n = Tp // C

    y, s_fin = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=C, num_chunks=n),
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, C, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, C, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, C, V), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, C, K), lambda b, h, t: (b, h, t, 0)),
            # u as (H, 1, K): a block's last two dims must equal the
            # array's or divide by (8, 128), and (1, K) over (H, K) does not
            pl.BlockSpec((1, 1, K), lambda b, h, t: (h, 0, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, V), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u[:, None, :], s0)
    if pad:
        y = y[:, :, :T]
    return y, s_fin
