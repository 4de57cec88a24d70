"""Operations and bytes that the model requires, from its sizes and shapes.

"Required" is what the mathematics of the model needs, not what the program
happens to compute:

* causal attention is counted as the triangle: query i attends to keys
  0..i, so a sequence of S tokens has S(S+1)/2 query-key pairs;
* prefill needs the logits of the last position only;
* decode reads the weights once per step and the cache at the filled
  positions only;
* recomputation (rematerialisation, the loss's recomputed logits) is not
  counted, nor are the elementwise operations of norms, softmax and
  activations (the usual convention for model FLOPs).

A later program that skips masked blocks, or stops writing every position's
logits, then raises its share of the peak without these counts moving.

``sizes`` is the dict :func:`model_sizes` makes from a configuration file.
"""
from __future__ import annotations

from typing import Any, Dict


def model_sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes these counts need, from an OLMo-style configuration file."""
    d = int(config["d_model"])
    heads = int(config["n_heads"])
    kv_heads = int(config.get("n_kv_heads") or heads)
    ffn = int(config["mlp_ratio"]) * d // 2  # SwiGLU: the projection is split in two
    return {
        "layers": int(config["n_layers"]),
        "d_model": d,
        "heads": heads,
        "kv_heads": kv_heads,
        "head_dim": d // heads,
        "ffn": ffn,
        "vocab": int(config["embedding_size"]),
        "weight_bytes": 2,  # bfloat16 weights
        "cache_bytes": 2,  # bfloat16 cache
    }


def layer_matmul_params(s: Dict[str, int]) -> int:
    """Weights one layer multiplies by: q, k, v, o and the three SwiGLU matrices."""
    d, hd = s["d_model"], s["head_dim"]
    attn = d * hd * (s["heads"] + 2 * s["kv_heads"]) + s["heads"] * hd * d
    return attn + 3 * d * s["ffn"]


def _attention_pairs_causal(seq: int) -> int:
    return seq * (seq + 1) // 2


def forward_flops(s: Dict[str, int], batch: int, seq: int, head_positions: int) -> float:
    """One forward pass over ``batch`` sequences of ``seq`` tokens, with the
    output head applied at ``head_positions`` positions of each sequence."""
    tokens = batch * seq
    dense = 2 * tokens * s["layers"] * layer_matmul_params(s)
    # QK^T and PV: 2 * head_dim each per query-key pair and head
    attn = s["layers"] * batch * 4 * s["heads"] * s["head_dim"] * _attention_pairs_causal(seq)
    head = 2 * batch * head_positions * s["d_model"] * s["vocab"]
    return float(dense + attn + head)


def train_step_flops(s: Dict[str, int], batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward's matmuls
    (the embedding lookup is a gather and counts nothing)."""
    return 3.0 * forward_flops(s, batch, seq, head_positions=seq)


def prefill_flops(s: Dict[str, int], batch: int, prompt: int) -> float:
    return forward_flops(s, batch, prompt, head_positions=1)


def decode_step_flops(s: Dict[str, int], batch: int, context: int) -> float:
    """One new token per sequence, attending to ``context`` keys (itself included)."""
    dense = 2 * batch * s["layers"] * layer_matmul_params(s)
    attn = s["layers"] * batch * 4 * s["heads"] * s["head_dim"] * context
    head = 2 * batch * s["d_model"] * s["vocab"]
    return float(dense + attn + head)


def decode_step_bytes(s: Dict[str, int], batch: int, context: int) -> float:
    """Weights read once (the tied embedding as the output head) plus K and V
    at the ``context`` filled positions of every layer."""
    weights = (s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d_model"]) * s[
        "weight_bytes"
    ]
    cache = (
        s["layers"] * 2 * batch * context * s["kv_heads"] * s["head_dim"] * s["cache_bytes"]
    )
    return float(weights + cache)


def generate_decode_mean(s: Dict[str, int], batch: int, prompt: int, new: int):
    """Mean (FLOPs, bytes) of the ``new - 1`` decode steps of one greedy
    generation: the step at position p attends to p + 1 keys."""
    steps = range(prompt, prompt + new - 1)
    n = len(steps)
    if n == 0:
        return 0.0, 0.0
    f = sum(decode_step_flops(s, batch, p + 1) for p in steps) / n
    b = sum(decode_step_bytes(s, batch, p + 1) for p in steps) / n
    return f, b
