"""AdamW + cosine schedule + global-norm clipping, from scratch (no optax).

State is a plain dict pytree {"m", "v", "step"} in fp32 regardless of param
dtype; ZeRO-1 sharding of m/v is applied by the caller via
``dist.sharding.zero1_specs``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    clip_norm: float = 1.0


def schedule(opt: OptConfig, step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(opt.warmup_steps, 1))
    prog = jnp.clip(
        (step - opt.warmup_steps) / max(opt.total_steps - opt.warmup_steps, 1), 0.0, 1.0
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = opt.min_lr_frac + (1 - opt.min_lr_frac) * cos
    return opt.lr * warm * frac


def adamw_init(params: Any) -> dict:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    return {
        "m": zeros,
        "v": jax.tree_util.tree_map(jnp.copy, zeros),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def adamw_update(
    grads: Any, state: dict, params: Any, opt: OptConfig
) -> Tuple[Any, dict, dict]:
    """Returns (new_params, new_state, metrics).  Runs under the
    ``optimizer`` named scope."""
    with jax.named_scope("optimizer"):
        return _adamw_update(grads, state, params, opt)


def _adamw_update(grads: Any, state: dict, params: Any, opt: OptConfig):
    step = state["step"]
    lr = schedule(opt, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))

    b1, b2 = opt.beta1, opt.beta2
    t = (step + 1).astype(jnp.float32)
    bc1 = 1 - b1**t
    bc2 = 1 - b2**t

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        mh = m2 / bc1
        vh = v2 / bc2
        delta = mh / (jnp.sqrt(vh) + opt.eps) + opt.weight_decay * p.astype(jnp.float32)
        p2 = p.astype(jnp.float32) - lr * delta
        return p2.astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_state = {
        "m": treedef.unflatten([o[1] for o in out]),
        "v": treedef.unflatten([o[2] for o in out]),
        "step": step + 1,
    }
    return new_params, new_state, {"lr": lr, "grad_norm": gnorm}
