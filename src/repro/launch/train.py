"""End-to-end training driver: control plane (Cross Wiring) + data plane.

The launcher mirrors the paper's running-stage workflow (§2.1):

1. **Scheduler / control plane** — the job is placed onto pods of the
   OCS cluster; its parallelism plan (TP/EP in-pod, DP ring across pods)
   becomes a logical-topology demand; MDMCF computes the OCS configuration
   (polynomial time) and reports LTRR + reconfiguration wall time.
2. **Data plane** — the sharded train step runs under the JAX mesh whose
   axes mirror the cluster (model=in-pod electrical, data/pod=across the
   optical core), with checkpointing and auto-resume.

The mesh is built over the devices present, as (data, model) with every
device on ``data``.  ``--smoke`` only chooses the reduced config, for CPU
runs; without it the published config is built, which has to fit the
devices present (``chip_smoke.py`` shows what fits one TPU v5e).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp

from .. import configs
from ..ckpt.manager import latest_step, restore_checkpoint, save_checkpoint
from ..core.logical import ring_demand
from ..core.reconfig import mdmcf_reconfigure
from ..core.topology import ClusterSpec
from ..models import get_api, smoke_config
from ..models.config import ModelConfig
from ..train.data import DataConfig, SyntheticData
from ..train.optimizer import OptConfig
from ..train.trainstep import TrainHparams, make_train_state, make_train_step
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def control_plane(arch: str, num_pods_used: int, cluster_pods: int = 8):
    """Place the job, derive its OCS demand, run MDMCF.  Returns a report."""
    spec = ClusterSpec(num_pods=cluster_pods, k_spine=16, k_leaf=16)
    plan = configs.get_plan(arch)
    pods = tuple(range(num_pods_used))
    demand = configs.job_demand(plan, spec, pods)
    t0 = time.perf_counter()
    res = mdmcf_reconfigure(spec, demand) if demand.any() else None
    dt = time.perf_counter() - t0
    return {
        "spec": spec,
        "plan": plan,
        "pods": pods,
        "demand_links": int(demand.sum() // 2),
        "ltrr": (res.ltrr if res is not None else 1.0),
        "reconfig_s": dt,
        "config": (res.config if res is not None else None),
    }


def run_train(
    cfg: ModelConfig,
    mesh,
    *,
    steps: int,
    batch: int,
    seq: int,
    lr: float = 3e-3,
    hp: TrainHparams = TrainHparams(),
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    log_every: int = 5,
) -> dict:
    """Train ``cfg`` on ``mesh`` with synthetic data and weights from seed 0.

    Resumes from ``ckpt_dir`` when it holds a checkpoint.  The host waits on
    the device only after the first step and at log, checkpoint and final
    steps, so preparing the next batch overlaps the running step.  Returns
    the final state, the loss of every step taken, the first step's wall time
    (compilation included) and the wall time of the steps after it, taken as
    one interval (``steady_s`` over ``steady_steps``)."""
    api = get_api(cfg)
    data = SyntheticData(
        DataConfig(vocab_size=cfg.vocab_size, batch=batch, seq=seq),
        model_cfg=cfg,
    )
    opt = OptConfig(lr=lr, warmup_steps=5, total_steps=max(steps, 10))
    b0 = data.batch_at(0)
    sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in b0.items()}
    step_fn, s_shard, _ = make_train_step(api, cfg, opt, mesh, hp, sds)

    key = jax.random.PRNGKey(0)
    start = 0
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        start = latest_step(ckpt_dir) + 1
        state = restore_checkpoint(
            ckpt_dir,
            jax.eval_shape(lambda: make_train_state(api, key)),
            shardings=s_shard,
        )
        print(f"[resume] from step {start - 1}")
    else:
        # born sharded, so the first step's donation is honoured
        state = jax.jit(lambda k: make_train_state(api, k), out_shardings=s_shard)(key)

    losses = []
    first_s = None
    pending = None
    t0 = time.perf_counter()
    for i in range(start, steps):
        # a profiler step per train step, for XProf's step view
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            batch_i = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, metrics = step_fn(state, batch_i)
        losses.append(metrics["loss"])
        if i == start:
            jax.block_until_ready(state)
            first_s = time.perf_counter() - t0
            t_steady = t_log = time.perf_counter()
            i_log = i
        if i % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            now = time.perf_counter()
            # tokens/s since the previous log line (the first step's includes compile)
            rate = (i - i_log) * batch * seq / (now - t_log) if i > i_log else (
                batch * seq / first_s
            )
            print(
                f"step {i:5d}  loss {loss:.4f}  "
                f"lr {float(metrics['lr']):.2e}  {rate:,.0f} tok/s"
            )
            t_log, i_log = now, i
        if ckpt_dir and (i + 1) % ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = save_checkpoint(ckpt_dir, i, state, background=True)
    jax.block_until_ready(state)
    steady_s = time.perf_counter() - t_steady if losses else 0.0
    if pending is not None:
        pending.join()
    if ckpt_dir:
        save_checkpoint(ckpt_dir, steps - 1, state)
        print(f"[ckpt] final at step {steps - 1}")
    return {
        "state": state,
        "losses": [float(x) for x in losses],
        "first_step_s": first_s,
        "steady_s": steady_s,
        "steady_steps": max(len(losses) - 1, 0),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--pods", type=int, default=2, help="pods the job occupies")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a profiler trace of the data plane here (XProf / TensorBoard)",
    )
    args = ap.parse_args()

    # ---- control plane ----------------------------------------------------
    cp = control_plane(args.arch, args.pods)
    print(
        f"[control-plane] arch={args.arch} pods={cp['pods']} "
        f"plan(tp={cp['plan'].tp}, ep={cp['plan'].ep}) "
        f"demand={cp['demand_links']} links  LTRR={cp['ltrr']:.3f} "
        f"mdmcf={cp['reconfig_s']*1e3:.1f} ms"
    )

    # ---- data plane ---------------------------------------------------------
    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    profile = jax.profiler.trace(args.profile_dir) if args.profile_dir else contextlib.nullcontext()
    with profile:
        run_train(
            cfg,
            make_host_mesh(),
            steps=args.steps,
            batch=args.batch,
            seq=args.seq,
            lr=args.lr,
            hp=TrainHparams(
                grad_accum=args.grad_accum,
                hierarchical=args.hierarchical,
                compress=args.compress,
                zero1=args.zero1,
            ),
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            log_every=args.log_every,
        )


if __name__ == "__main__":
    main()
