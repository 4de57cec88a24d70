"""Training cells: the program's jitted train step, driven from the seed.

Set-up builds one object, the compiled step with its state, born sharded as
``launch/train.run_train`` makes it, and drives it through its first
``check_steps`` steps on the same feed the window uses.  Those steps compile
the step and are the ones the reference follows.  The same state then goes
on into the window.  The host loop mirrors ``run_train``: it never waits for
the step it has just dispatched, only for the one ``lead_steps`` back (some
seconds of steps), so the device stays fed while the host stands still, and
once the window's time is up it dispatches nothing more and waits for all
that it sent.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .spec import host_rng
from .window import Run, leaf_paths


class TrainFeed:
    """Rows of uniform token ids from the seed, every row new; targets are
    the ids shifted by one."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.rng = host_rng(seed, "train-rows")
        self.batch, self.seq, self.vocab = batch, seq, vocab

    def next_host(self):
        ids = self.rng.integers(0, self.vocab, size=(self.batch, self.seq + 1), dtype=np.int32)
        return ids[:, :-1], ids[:, 1:]


def rows_of(seed: int, traffic, vocab: int, steps: int):
    """The first ``steps`` batches of a run with this seed, on the host."""
    feed = TrainFeed(seed, traffic["batch"], traffic["seq"], vocab)
    return [feed.next_host() for _ in range(steps)]


def build(run: Run, break_step=None):
    """The program's step, its state from the seed, and its batch sharding.

    ``break_step`` wraps the step (tests plant faults with it)."""
    from repro.launch.mesh import make_mesh
    from repro.models import get_api
    from repro.train.optimizer import OptConfig
    from repro.train.trainstep import TrainHparams, make_train_state, make_train_step

    t = run.cell.traffic
    cfg = run.program_config()
    api = get_api(cfg)
    mesh = make_mesh(tuple(t["mesh"]["shape"]), tuple(t["mesh"]["axes"]), devices=run.devices)
    opt = OptConfig(**t["optimizer"])
    hp = TrainHparams(**t["hparams"])
    sds = {
        k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32) for k in ("tokens", "targets")
    }
    step, s_shard, b_shard = make_train_step(api, cfg, opt, mesh, hp, sds)
    if break_step is not None:
        step = break_step(step)
    key = run.weight_key()
    state = jax.jit(lambda k: make_train_state(api, k), out_shardings=s_shard)(key)
    init = jax.jit(api.init, out_shardings=s_shard["params"])
    return step, state, b_shard, init, key


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).reshape(-1)) for x in jax.tree_util.tree_leaves(tree)])


def run_cell(run: Run, break_step=None) -> Dict:
    t = run.cell.traffic
    B, S = t["batch"], t["seq"]
    beta1 = t["optimizer"]["beta1"]
    n_check = int(t["check_steps"])
    lead = int(t["lead_steps"])
    step, state, b_shard, init, key = build(run, break_step)
    paths = leaf_paths(state["params"])
    feed = TrainFeed(run.seed, B, S, run.token_vocab())

    def device_batch():
        tokens, targets = feed.next_host()
        return jax.device_put({"tokens": tokens, "targets": targets}, b_shard)

    # the first steps: compile, and give the reference its readings
    m_norms = jax.jit(lambda m: leaf_norms(m) / (1 - beta1))
    change_norms = jax.jit(
        lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, init(k)
        ))
    )
    losses = []
    grad_norms = None
    for i in range(n_check):
        state, metrics = step(state, device_batch())
        losses.append(metrics["loss"])
        if i == 0:
            grad_norms = m_norms(state["opt"]["m"])
    update_norms = change_norms(state["params"], key)
    readings = {
        "losses": [float(x) for x in losses],
        "grad_norms": dict(zip(paths, map(float, np.asarray(grad_norms)))),
        "update_norms": dict(zip(paths, map(float, np.asarray(update_norms)))),
    }
    jax.block_until_ready(state)

    # the window
    pending: collections.deque = collections.deque()
    run.start_window()
    t0 = time.perf_counter()
    n = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            state, metrics = step(state, device_batch())
        n += 1
        pending.append(metrics["loss"])
        if len(pending) > lead:
            with jax.profiler.TraceAnnotation("bench.wait_lead_back"):
                pending.popleft().block_until_ready()
        if time.perf_counter() - t0 >= run.seconds:
            break
    with jax.profiler.TraceAnnotation("bench.wait_end"):
        jax.block_until_ready(state)
    t1 = time.perf_counter()
    run.end_window(t1 - t0)

    if run.trace:
        run.note_memory_analysis(step, state, device_batch())
    run.read_memory_peak()
    del state, metrics, pending
    run.free_program()

    per_chip = run.reference().train_step_flops(run.cell.config, B, S) / len(run.devices)
    return {
        "attempted": n,
        "failed": 0,
        "end_to_end": {"train_tok_s": n * B * S / (t1 - t0)},
        "required": {"train_step": {"flops": per_chip}},
        "readings": readings,
    }


def check(run: Run, out: Dict) -> Dict[str, float]:
    """Numbers compared with the reference, by name."""
    ref_mod = run.reference()
    t = run.cell.traffic
    batches = rows_of(run.seed, t, run.token_vocab(), int(t["check_steps"]))
    ref = ref_mod.train_readings(run.cell.config, run.seed, batches, t["optimizer"], run.devices)
    return compare(out["readings"], ref)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Loss gap of each step, and the worst leaf's gap of the first gradient's
    norm and of the weights' change, each against the larger of the leaf's
    own reference norm and the median leaf's."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_gap.s{i + 1}"] = abs(lp - lr) / abs(lr)
    out["grad_gap"] = worst_leaf(prog["grad_norms"], ref["grad_norms"])
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change by a rule on the gradient
    raw = ref["raw_grad_norms"]
    med = float(np.median(list(raw.values())))
    moving = [k for k in raw if raw[k] >= 1e-3 * med]
    change = leaf_gaps(
        {k: prog["update_norms"][k] for k in moving},
        {k: ref["update_norms"][k] for k in moving},
    )
    out["update_gap"] = max(change)
    return out


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float]):
    """Per leaf, the gap between the two norms over the larger of the leaf's
    reference norm and the median leaf's."""
    if set(prog) != set(ref):
        return [float("inf")]
    med = float(np.median(list(ref.values())))
    return [abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref]


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    return max(leaf_gaps(prog, ref))
