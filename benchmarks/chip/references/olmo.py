"""Plain reference of OLMo (arXiv:2402.00838), in float32 jax.numpy.

It follows the published description and imports nothing of the program:

* token embedding, tied with the output head (``weight_tying``);
* per layer, pre-norm: x + Attn(LN(x)), then x + SwiGLU(LN(x));
* LN is LayerNorm without scale or bias (``layer_norm_with_affine`` false);
* multi-head causal attention, no biases, rotary embeddings on q and k
  (the half-split rotation, theta from the configuration);
* SwiGLU: (silu(h Wg) * (h Wi)) Wo, hidden ``mlp_ratio * d_model / 2``;
* a final LN, then logits h E^T.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise made of bfloat16 passes.

Weights are made from the run's seed, layer by layer, by the same sequence of
``jax.random`` calls as the program's initialiser, and stored as the
configuration states them (bfloat16), then widened to float32.  The
reference therefore shares the program's starting point without taking any
array the program made.

``precision="fp8"`` is the control: the same mathematics with both operands
of every matrix product rounded to fp8, element by element: e4m3 in the
forward pass and, for the gradient that flows back into a product, e5m2 (the
usual fp8 training recipe).  Each tensor is scaled so that its largest
magnitude meets the format's largest before it is rounded, so nothing within
2**-15 of a tensor's largest value is flushed to zero: softmax probabilities
and small gradients keep their place.  It is the next precision below the
configuration's bfloat16, and the benchmark has to judge it not correct.

The LayerNorm eps is the configuration file's: the program's 1e-6, where
OLMo's own code uses 1e-5 (``PERF.md`` lists it as an open question).

This module is the only reader of OLMo's configuration keys.  Beside the
reference it gives the harness what the published sizes fix: the program's
fields (``program_fields``), the two ranges of token ids (``vocab``), and
the work the cells require (``train_step_flops``, ``prefill_flops``,
``decode_step_work``), counted by the conventions of ``harness/flops.py``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness.flops import causal_pairs

HIGHEST = jax.lax.Precision.HIGHEST

# names of the weights, as the program's checkpoints name them
EMBED = "embed/tok"
LAYER_LEAVES = (
    "units/l0/mix/wq",
    "units/l0/mix/wk",
    "units/l0/mix/wv",
    "units/l0/mix/wo",
    "units/l0/ffn/wi",
    "units/l0/ffn/wg",
    "units/l0/ffn/wo",
)


def sizes(config) -> Dict:
    """The sizes of an OLMo configuration file, under the names used here."""
    d = int(config["d_model"])
    heads = int(config["n_heads"])
    return {
        "layers": int(config["n_layers"]),
        "d": d,
        "heads": heads,
        "kv_heads": int(config["n_kv_heads"] or heads),
        "head_dim": d // heads,
        "ffn": int(config["mlp_ratio"]) * d // 2,  # SwiGLU: the projection is split in two
        "ids": int(config["vocab_size"]),
        "vocab": int(config["embedding_size"]),
        "tied": bool(config["weight_tying"]),
        "mlp": config["activation_type"],
        "theta": float(config["assumed"]["rope_theta"]),
        "eps": float(config["assumed"]["layer_norm_eps"]),
        "dtype": jnp.dtype(config["assumed"]["dtype"]),
    }


# ---------------------------------------------------------------------------
# what the harness takes from the configuration
# ---------------------------------------------------------------------------

def program_fields(config) -> Dict:
    """The program's ``ModelConfig`` fields that the published sizes fix."""
    s = sizes(config)
    return {
        "num_layers": s["layers"],
        "d_model": s["d"],
        "num_heads": s["heads"],
        "num_kv_heads": s["kv_heads"],
        "d_ff": s["ffn"],
        "vocab_size": s["vocab"],
        "tie_embeddings": s["tied"],
        "norm_kind": "nonparametric",
        "mlp_kind": s["mlp"],
        "rope_theta": s["theta"],
        "param_dtype": s["dtype"].name,
        "compute_dtype": s["dtype"].name,
    }


def vocab(config):
    """(ids, served): token ids are drawn below the tokenizer's vocabulary,
    served ids must lie below the embedding's rows (the padded vocabulary)."""
    s = sizes(config)
    return s["ids"], s["vocab"]


def layer_matmul_params(s) -> int:
    """Weights one layer multiplies by: q, k, v, o and the three SwiGLU matrices."""
    d, hd = s["d"], s["head_dim"]
    attn = d * hd * (s["heads"] + 2 * s["kv_heads"]) + s["heads"] * hd * d
    return attn + 3 * d * s["ffn"]


def forward_flops(s, batch: int, seq: int, head_positions: int) -> float:
    """One forward pass over ``batch`` sequences of ``seq`` tokens, with the
    output head applied at ``head_positions`` positions of each sequence."""
    tokens = batch * seq
    dense = 2 * tokens * s["layers"] * layer_matmul_params(s)
    # QK^T and PV: 2 * head_dim each per query-key pair and head
    attn = s["layers"] * batch * 4 * s["heads"] * s["head_dim"] * causal_pairs(seq)
    head = 2 * batch * head_positions * s["d"] * s["vocab"]
    return float(dense + attn + head)


def train_step_flops(config, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward's matmuls
    (the embedding lookup is a gather and counts nothing)."""
    return 3.0 * forward_flops(sizes(config), batch, seq, head_positions=seq)


def prefill_flops(config, batch: int, prompt: int) -> float:
    return forward_flops(sizes(config), batch, prompt, head_positions=1)


def decode_step_work(config, batch: int, context: int):
    """(FLOPs, bytes) of one new token per sequence, attending to ``context``
    keys (itself included): the weights read once (the tied embedding as the
    output head) plus K and V at the ``context`` filled positions of every
    layer."""
    s = sizes(config)
    dense = 2 * batch * s["layers"] * layer_matmul_params(s)
    attn = s["layers"] * batch * 4 * s["heads"] * s["head_dim"] * context
    head = 2 * batch * s["d"] * s["vocab"]
    item = s["dtype"].itemsize  # weights and cache as the configuration stores them
    weights = (s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d"]) * item
    cache = s["layers"] * 2 * batch * context * s["kv_heads"] * s["head_dim"] * item
    return float(dense + attn + head), float(weights + cache)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _stored(w, s):
    """As the configuration stores weights (bfloat16), widened to float32."""
    return w.astype(s["dtype"]).astype(jnp.float32)


def _dense(key, n_in, n_out, s):
    w = jax.random.normal(key, (n_in, n_out)) * (1.0 / math.sqrt(n_in))
    return _stored(w, s)


def _layer(key, s):
    d, f = s["d"], s["ffn"]
    hq = s["heads"] * s["head_dim"]
    (key,) = jax.random.split(key, 1)  # one layer in each repeated unit
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    m = jax.random.split(ks[1], 3)
    return {
        "units/l0/mix/wq": _dense(a[0], d, hq, s),
        "units/l0/mix/wk": _dense(a[1], d, hq, s),
        "units/l0/mix/wv": _dense(a[2], d, hq, s),
        "units/l0/mix/wo": _dense(a[3], hq, d, s),
        "units/l0/ffn/wi": _dense(m[0], d, f, s),
        "units/l0/ffn/wg": _dense(m[1], d, f, s),
        "units/l0/ffn/wo": _dense(m[2], f, d, s),
    }


def make_weights(config, seed: int) -> Dict[str, jnp.ndarray]:
    """float32 weights, layer leaves stacked over layers."""
    s = sizes(config)

    @jax.jit
    def build(key):
        k_embed, _, k_units = jax.random.split(key, 3)
        emb = jax.random.normal(k_embed, (s["vocab"], s["d"])) * 0.02
        w = jax.vmap(lambda k: _layer(k, s))(jax.random.split(k_units, s["layers"]))
        w[EMBED] = _stored(emb, s)
        return w

    return build(seed_key(seed))


# ---------------------------------------------------------------------------
# matrix products at the chosen precision
# ---------------------------------------------------------------------------

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2


def _fp8(x, dtype):
    """``x`` rounded to ``dtype`` per element, under one scale that maps its
    largest magnitude to the format's largest."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _f8einsum(spec, a, b):
    return jnp.einsum(spec, _fp8(a, E4M3), _fp8(b, E4M3), precision=HIGHEST)


def _f8einsum_fwd(spec, a, b):
    return _f8einsum(spec, a, b), (a, b)


def _f8einsum_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), _fp8(a, E4M3), _fp8(b, E4M3)
    )
    return vjp(_fp8(g, E5M2))


_f8einsum.defvjp(_f8einsum_fwd, _f8einsum_bwd)


def _einsum_for(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _f8einsum
    raise ValueError(precision)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x: (B, S, H, Dh); the first and second halves of Dh rotate together."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(s, ein, x, w):
    B, S, d = x.shape
    H, dh = s["heads"], s["head_dim"]
    h = _layer_norm(x, s["eps"])
    q = ein("bsd,de->bse", h, w["units/l0/mix/wq"]).reshape(B, S, H, dh)
    k = ein("bsd,de->bse", h, w["units/l0/mix/wk"]).reshape(B, S, H, dh)
    v = ein("bsd,de->bse", h, w["units/l0/mix/wv"]).reshape(B, S, H, dh)
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    scores = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = ein("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * dh)
    x = x + ein("bse,ed->bsd", o, w["units/l0/mix/wo"])
    h = _layer_norm(x, s["eps"])
    gate = jax.nn.silu(ein("bsd,df->bsf", h, w["units/l0/ffn/wg"]))
    up = ein("bsd,df->bsf", h, w["units/l0/ffn/wi"])
    return x + ein("bsf,fd->bsd", gate * up, w["units/l0/ffn/wo"])


def hidden(s, precision: str, w, tokens, remat: bool = False):
    """Final-norm hidden states (B, S, d) of ``tokens`` (B, S)."""
    ein = _einsum_for(precision)
    x = w[EMBED][tokens]
    layers = {k: w[k] for k in LAYER_LEAVES}

    def body(x, lw):
        return _block(s, ein, x, lw), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    return _layer_norm(x, s["eps"])


def logits_at(s, precision: str, w, tokens, positions):
    """Logits (B, len(positions), V) at the given positions of each row."""
    ein = _einsum_for(precision)
    h = hidden(s, precision, w, tokens)[:, positions, :]
    return ein("bsd,vd->bsv", h, w[EMBED])


def loss(s, precision: str, w, tokens, targets, chunk: int = 256):
    """Mean next-token cross-entropy over every position of every row."""
    ein = _einsum_for(precision)
    h = hidden(s, precision, w, tokens, remat=True)
    B, S, d = h.shape
    chunk = min(chunk, S)
    hc = h.reshape(B, S // chunk, chunk, d).swapaxes(0, 1)
    tc = targets.reshape(B, S // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def nll(hx, tx):
        z = ein("bsd,vd->bsv", hx, w[EMBED])
        gold = jnp.take_along_axis(z, tx[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    total, _ = jax.lax.scan(lambda c, xs: (c + nll(*xs), None), 0.0, (hc, tc))
    return total / (B * S)


# ---------------------------------------------------------------------------
# serving: the logits behind every served token
# ---------------------------------------------------------------------------

def served_gaps(config, seed: int, prompts, served, block: int, precision: str = "f32"):
    """For every served token, by how much its reference logit lies below the
    reference's best at that position.

    ``prompts`` (R, P) and ``served`` (R, N): the token served at step j was
    chosen at position P - 1 + j.  With ``precision="fp8"`` the token read
    is the one the fp8 control puts first there, and its gap is read from
    the float32 logits: the control in the program's place.

    Returns an (R, N) array of gaps."""
    s = sizes(config)
    w = make_weights(config, seed)
    R, Pn = prompts.shape
    N = served.shape[1]
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    positions = np.arange(Pn - 1, Pn - 1 + N)

    @jax.jit
    def gaps(w, toks, chosen):
        ref = logits_at(s, "f32", w, toks, positions)
        if precision != "f32":
            chosen = jnp.argmax(logits_at(s, precision, w, toks, positions), axis=-1)
        picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
        return jnp.max(ref, axis=-1) - picked

    out = []
    for lo in range(0, R, block):
        hi = min(R, lo + block)
        out.append(np.asarray(gaps(w, seqs[lo:hi], served[lo:hi].astype(np.int32))))
    return np.concatenate(out, axis=0)


# ---------------------------------------------------------------------------
# training: three AdamW steps
# ---------------------------------------------------------------------------

def _lr(opt, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_frac`` (step counts from 0)."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = (step - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = min(max(prog, 0.0), 1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def train_readings(
    config, seed: int, batches: Sequence, opt, devices, precision: str = "f32"
) -> Dict:
    """Run len(batches) AdamW steps from the seed's weights.

    ``batches`` holds (tokens, targets) pairs of int arrays (B, S).  The
    batch is split over ``devices`` by rows; the weights and Adam's moments
    are replicated.  Returns each step's loss, the per-leaf norm of the first
    step's gradient after clipping (what the optimizer is given), and the
    per-leaf norm of the weights' change over all the steps."""
    s = sizes(config)
    mesh = Mesh(np.array(devices), ("rows",))
    rows = NamedSharding(mesh, P("rows"))
    rep = NamedSharding(mesh, P())
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"]

    grad_fn = jax.jit(
        jax.value_and_grad(lambda w, t, y: loss(s, precision, w, t, y)),
        in_shardings=(rep, rows, rows),
        out_shardings=(rep, rep),
    )

    @partial(jax.jit, donate_argnums=(0, 1, 2), out_shardings=(rep, rep, rep, rep))
    def update(w, m, v, g, lr, t):
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
        clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1**t, 1 - b2**t
        w = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps) + wd * p), w, m, v
        )
        norms = {k: jnp.linalg.norm(x.reshape(-1)) for k, x in g.items()}
        return w, m, v, norms

    norms_of = jax.jit(lambda t: {k: jnp.linalg.norm(x.reshape(-1)) for k, x in t.items()})
    diff_norms = jax.jit(
        lambda a, b: {k: jnp.linalg.norm((a[k] - b[k]).reshape(-1)) for k in a}
    )

    w = jax.device_put(make_weights(config, seed), rep)
    w0_key = seed
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses: List[float] = []
    first_grad = raw_grad = None
    for step, (tokens, targets) in enumerate(batches):
        value, g = grad_fn(w, np.asarray(tokens, np.int32), np.asarray(targets, np.int32))
        losses.append(float(value))
        if step == 0:
            raw_grad = {k: float(x) for k, x in norms_of(g).items()}
        w, m, v, clipped = update(w, m, v, g, _lr(opt, step), float(step + 1))
        if step == 0:
            first_grad = {k: float(x) for k, x in clipped.items()}
        del g
    del m, v
    w0 = jax.device_put(make_weights(config, w0_key), rep)
    change = {k: float(x) for k, x in diff_norms(w, w0).items()}
    return {
        "losses": losses,
        "grad_norms": first_grad,
        "raw_grad_norms": raw_grad,
        "update_norms": change,
    }
