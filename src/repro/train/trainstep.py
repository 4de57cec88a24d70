"""Distributed train steps.

Two interchangeable step builders:

* :func:`make_pjit_step` — the *paper-faithful baseline* data plane: plain
  pjit/GSPMD; the DP gradient reduction lowers to one flat all-reduce over
  (pod × data).  Cross-pod bytes = full gradient size.

* :func:`make_hierarchical_step` — the beyond-paper optimized data plane:
  `jax.shard_map` manual over the DP axes (model axis stays auto/GSPMD).
  Per-leaf reduce-scatter in-pod → (optionally int8-compressed) cross-pod
  all-reduce → ZeRO-1 optimizer update on the gradient *shard* → in-pod
  all-gather of the updated parameters.  Cross-pod bytes shrink by the
  in-pod DP width (16×) and optimizer memory by the same factor.

Both support gradient-accumulation microbatching via ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dist.sharding import (
    batch_specs,
    mesh_axis_sizes,
    param_specs,
    shard_map_dp,
    to_shardings,
    zero1_dim,
    zero1_specs,
    _path_str,
)
from ..launch.mesh import dp_axes
from .optimizer import OptConfig, adamw_init, adamw_update, global_norm, schedule


@dataclasses.dataclass(frozen=True)
class TrainHparams:
    grad_accum: int = 1
    hierarchical: bool = False  # shard_map hierarchical collectives
    compress: bool = False  # int8 cross-pod gradient compression
    zero1: bool = False  # shard optimizer state over data axis
    fsdp: bool = False  # ZeRO-3: shard params over data; gather per layer


def make_train_state(api, key) -> dict:
    params = api.init(key)
    return {"params": params, "opt": adamw_init(params)}


def train_state_specs(state_shape: dict, mesh, cfg, hp: TrainHparams):
    pspecs = param_specs(state_shape["params"], mesh, cfg, fsdp=hp.fsdp)
    if hp.zero1 or hp.hierarchical or hp.fsdp:
        # fsdp runs shard the fp32 moments over (data, pod) — with params
        # already data-sharded, the moments are the HBM bottleneck
        mspecs = zero1_specs(state_shape["opt"]["m"], mesh, cfg, use_pod=hp.fsdp)
        vspecs = zero1_specs(state_shape["opt"]["v"], mesh, cfg, use_pod=hp.fsdp)
    else:
        mspecs = param_specs(state_shape["opt"]["m"], mesh, cfg)
        vspecs = param_specs(state_shape["opt"]["v"], mesh, cfg)
    return {
        "params": pspecs,
        "opt": {"m": mspecs, "v": vspecs, "step": P()},
    }


def _accum_grads(loss_fn, params, batch, n_micro: int):
    """Gradient accumulation over microbatches with lax.scan."""
    if n_micro <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def micro(b):
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]), b
        )

    mb = micro(batch)

    def step(carry, b):
        loss_acc, g_acc = carry
        loss, g = jax.value_and_grad(loss_fn)(params, b)
        return (
            loss_acc + loss / n_micro,
            jax.tree_util.tree_map(lambda a, x: a + x / n_micro, g_acc, g),
        ), None

    zeros = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(step, (jnp.zeros(()), zeros), mb)
    return loss, grads


# ---------------------------------------------------------------------------
# baseline: plain pjit
# ---------------------------------------------------------------------------

def make_pjit_step(api, cfg, opt: OptConfig, mesh, hp: TrainHparams, batch_shape):
    """Returns (jitted step, state_shardings, batch_shardings)."""
    state_shape = jax.eval_shape(lambda k: make_train_state(api, k), jax.random.PRNGKey(0))
    sspecs = train_state_specs(state_shape, mesh, cfg, hp)
    s_shard = to_shardings(sspecs, mesh)
    b_shard = to_shardings(batch_specs(batch_shape, mesh), mesh)

    def step(state, batch):
        loss, grads = _accum_grads(
            lambda p, b: api.loss(p, b), state["params"], batch, hp.grad_accum
        )
        new_params, new_opt, metrics = adamw_update(
            grads, state["opt"], state["params"], opt
        )
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    jitted = jax.jit(
        step,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jitted, s_shard, b_shard


# ---------------------------------------------------------------------------
# optimized: hierarchical shard_map + ZeRO-1 (+ int8 cross-pod compression)
# ---------------------------------------------------------------------------

def make_hierarchical_step(api, cfg, opt: OptConfig, mesh, hp: TrainHparams, batch_shape):
    """shard_map over DP axes; model axis remains auto (GSPMD)."""
    sizes = mesh_axis_sizes(mesh)
    dp = dp_axes(mesh)
    data_size = sizes.get("data", 1)
    has_pod = "pod" in sizes
    pod_size = sizes.get("pod", 1)
    n_dp = data_size * pod_size
    model_size = sizes.get("model", 1)
    in_moe = cfg.moe is not None

    state_shape = jax.eval_shape(lambda k: make_train_state(api, k), jax.random.PRNGKey(0))
    sspecs = train_state_specs(state_shape, mesh, cfg, hp)
    s_shard = to_shardings(sspecs, mesh)
    bspecs = batch_specs(batch_shape, mesh)
    b_shard = to_shardings(bspecs, mesh)

    # manual (DP-axes-only) views of the same specs
    dp_set = set(dp)

    def _dp_only_spec(s: P) -> P:
        out = []
        for a in s:
            if a is None:
                out.append(None)
            elif isinstance(a, (tuple, list)):
                kept = tuple(x for x in a if x in dp_set)
                out.append(kept if kept else None)
            else:
                out.append(a if a in dp_set else None)
        return P(*out)

    def dp_only(spec_tree):
        return jax.tree_util.tree_map(
            _dp_only_spec, spec_tree, is_leaf=lambda x: isinstance(x, P)
        )

    params_dp = jax.tree_util.tree_map(
        lambda s: P(*[None] * len(s)), sspecs["params"],
        is_leaf=lambda x: isinstance(x, P),
    )
    opt_dp = dp_only(sspecs["opt"])
    batch_dp = dp_only(bspecs)

    # per-leaf scatter dims (must match zero1_specs)
    leaf_paths = [
        _path_str(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(state_shape["params"])[0]
    ]
    leaf_shapes = [
        tuple(l.shape)
        for l in jax.tree_util.tree_leaves(state_shape["params"])
    ]
    scatter_dims = [
        zero1_dim(p, s, model_size, data_size, in_moe)
        for p, s in zip(leaf_paths, leaf_shapes)
    ]
    treedef = jax.tree_util.tree_structure(state_shape["params"])

    # named ``step`` so that its module reads jit_step(<id>), like the pjit
    # step's; the collectives run under grad_sync/{reduce_scatter,
    # pod_allreduce,all_gather}, the AdamW block under ``optimizer``
    def step(state, batch):
        params = state["params"]
        loss, grads = _accum_grads(
            lambda p, b: api.loss(p, b), params, batch, hp.grad_accum
        )

        flat_g = treedef.flatten_up_to(grads)
        flat_p = treedef.flatten_up_to(params)
        flat_m = treedef.flatten_up_to(state["opt"]["m"])
        flat_v = treedef.flatten_up_to(state["opt"]["v"])
        step_ = state["opt"]["step"]

        # ---- global grad norm from shards (no extra gather) -------------
        with jax.named_scope("grad_sync"):
            loss = jax.lax.pmean(loss, dp)
            sq = jnp.zeros(())
            shards = []
            for g, dim in zip(flat_g, scatter_dims):
                g = g.astype(jnp.float32)
                with jax.named_scope("reduce_scatter"):
                    if dim is not None:
                        gs = jax.lax.psum_scatter(g, "data", scatter_dimension=dim, tiled=True)
                    else:
                        gs = jax.lax.psum(g, "data")
                if has_pod:
                    with jax.named_scope("pod_allreduce"):
                        if hp.compress:
                            scale = jnp.maximum(
                                jax.lax.pmax(jnp.max(jnp.abs(gs)), "pod"), 1e-12
                            )
                            q = jnp.clip(jnp.round(gs / scale * 127.0), -127, 127)
                            gs = jax.lax.psum(q.astype(jnp.int32), "pod").astype(
                                jnp.float32
                            ) * (scale / 127.0)
                        else:
                            gs = jax.lax.psum(gs, "pod")
                gs = gs / n_dp
                shards.append(gs)
                part = jnp.sum(gs * gs)
                if dim is not None:
                    part = jax.lax.psum(part, "data")
                sq = sq + part
            gnorm = jnp.sqrt(sq)

        with jax.named_scope("optimizer"):
            clip = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))
            lr = schedule(opt, step_)
            b1, b2 = opt.beta1, opt.beta2
            t = (step_ + 1).astype(jnp.float32)
            bc1, bc2 = 1 - b1**t, 1 - b2**t

            new_p, new_m, new_v = [], [], []
            for g, p, m, v, dim in zip(shards, flat_p, flat_m, flat_v, scatter_dims):
                g = g * clip
                if dim is not None:
                    idx = jax.lax.axis_index("data")
                    size = p.shape[dim] // data_size
                    p_shard = jax.lax.dynamic_slice_in_dim(p, idx * size, size, axis=dim)
                else:
                    p_shard = p
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + opt.eps)
                upd = upd + opt.weight_decay * p_shard.astype(jnp.float32)
                p2 = (p_shard.astype(jnp.float32) - lr * upd).astype(p.dtype)
                if dim is not None:
                    with jax.named_scope("grad_sync"), jax.named_scope("all_gather"):
                        p2 = jax.lax.all_gather(p2, "data", axis=dim, tiled=True)
                new_p.append(p2)
                new_m.append(m2)
                new_v.append(v2)

        new_state = {
            "params": treedef.unflatten(new_p),
            "opt": {
                "m": treedef.unflatten(new_m),
                "v": treedef.unflatten(new_v),
                "step": step_ + 1,
            },
        }
        return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    state_in_specs = {"params": params_dp, "opt": opt_dp}
    sm = shard_map_dp(
        step,
        mesh,
        in_specs=(state_in_specs, batch_dp),
        out_specs=(state_in_specs, P()),
        manual_axes=dp,
    )
    jitted = jax.jit(
        sm,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jitted, s_shard, b_shard


def make_train_step(api, cfg, opt: OptConfig, mesh, hp: TrainHparams, batch_shape):
    if hp.hierarchical:
        return make_hierarchical_step(api, cfg, opt, mesh, hp, batch_shape)
    return make_pjit_step(api, cfg, opt, mesh, hp, batch_shape)
