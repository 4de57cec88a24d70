"""Conventions for the operations and bytes that a model requires.

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

Each family's module (``references/<name>.py``) counts its own work by these
conventions, from its own configuration file; what is here takes plain
numbers only.
"""
from __future__ import annotations

from typing import Callable, Tuple


def causal_pairs(seq: int) -> int:
    """Query-key pairs of causal attention over ``seq`` tokens."""
    return seq * (seq + 1) // 2


def generate_decode_mean(
    step_work: Callable[[int], Tuple[float, float]], prompt: int, new: int
) -> Tuple[float, float]:
    """Mean (FLOPs, bytes) of the ``new - 1`` decode steps of one greedy
    generation after a prompt of ``prompt`` tokens: the step at position p
    attends to p + 1 keys.  ``step_work(context)`` is one step's work."""
    work = [step_work(p + 1) for p in range(prompt, prompt + new - 1)]
    if not work:
        return 0.0, 0.0
    return sum(f for f, _ in work) / len(work), sum(b for _, b in work) / len(work)
