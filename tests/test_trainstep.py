"""Distributed train steps on the host mesh: loss decreases, grad-accum
equivalence, hierarchical (shard_map) path agrees with plain pjit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models import get_api, smoke_config
from repro.train.data import DataConfig, SyntheticData
from repro.train.optimizer import OptConfig
from repro.train.trainstep import TrainHparams, make_train_state, make_train_step


def _setup(arch="olmo-1b", batch=8, seq=32):
    cfg = smoke_config(arch)
    api = get_api(cfg)
    mesh = make_host_mesh()
    data = SyntheticData(
        DataConfig(vocab_size=cfg.vocab_size, batch=batch, seq=seq, seed=0),
        model_cfg=cfg,
    )
    return cfg, api, mesh, data


def _sds(batch):
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}


def test_loss_decreases():
    cfg, api, mesh, data = _setup()
    opt = OptConfig(lr=5e-3, warmup_steps=5, total_steps=200, weight_decay=0.0)
    hp = TrainHparams()
    b0 = data.batch_at(0)
    step, s_shard, b_shard = make_train_step(api, cfg, opt, mesh, hp, _sds(b0))
    state = make_train_state(api, jax.random.PRNGKey(0))
    losses = []
    for i in range(40):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses


def test_grad_accum_equivalence():
    """grad_accum=2 must match grad_accum=1 on the same global batch."""
    cfg, api, mesh, data = _setup(batch=8, seq=16)
    opt = OptConfig(lr=1e-3, warmup_steps=1, weight_decay=0.0)
    b0 = data.batch_at(0)
    outs = []
    for accum in (1, 2):
        hp = TrainHparams(grad_accum=accum)
        step, *_ = make_train_step(api, cfg, opt, mesh, hp, _sds(b0))
        state = make_train_state(api, jax.random.PRNGKey(1))
        batch = {k: jnp.asarray(v) for k, v in b0.items()}
        state, m = step(state, batch)
        outs.append((state, float(m["loss"])))
    assert outs[0][1] == pytest.approx(outs[1][1], rel=1e-4)
    w1 = jax.tree_util.tree_leaves(outs[0][0]["params"])
    w2 = jax.tree_util.tree_leaves(outs[1][0]["params"])
    for a, b in zip(w1, w2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-5
        )


def test_hierarchical_matches_pjit():
    """The shard_map hierarchical step must produce the same update as the
    pjit baseline (1-device mesh: collectives are identities)."""
    cfg, api, mesh, data = _setup(batch=4, seq=16)
    opt = OptConfig(lr=1e-3, warmup_steps=1, weight_decay=0.01)
    b0 = data.batch_at(0)
    states = []
    for hier in (False, True):
        hp = TrainHparams(hierarchical=hier, zero1=True)
        step, *_ = make_train_step(api, cfg, opt, mesh, hp, _sds(b0))
        state = make_train_state(api, jax.random.PRNGKey(2))
        batch = {k: jnp.asarray(v) for k, v in b0.items()}
        state, m = step(state, batch)
        states.append((state, float(m["loss"])))
    assert states[0][1] == pytest.approx(states[1][1], rel=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(states[0][0]["params"]),
        jax.tree_util.tree_leaves(states[1][0]["params"]),
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=3e-5
        )


def test_train_resume_from_checkpoint(tmp_path):
    """Checkpoint mid-run, restart, continue: the loss stream must continue
    exactly (deterministic data + bitwise state restore)."""
    from repro.ckpt.manager import restore_checkpoint, save_checkpoint

    cfg, api, mesh, data = _setup(batch=4, seq=16)
    opt = OptConfig(lr=1e-3, warmup_steps=2, weight_decay=0.0)
    hp = TrainHparams()
    b0 = data.batch_at(0)
    step, *_ = make_train_step(api, cfg, opt, mesh, hp, _sds(b0))

    state = make_train_state(api, jax.random.PRNGKey(0))
    ref_losses = []
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state, m = step(state, batch)
        ref_losses.append(float(m["loss"]))
        if i == 2:
            save_checkpoint(str(tmp_path), i, state)

    # crash + restart after step 2
    state2 = restore_checkpoint(
        str(tmp_path), jax.eval_shape(lambda: make_train_state(api, jax.random.PRNGKey(0)))
    )
    resumed = []
    for i in range(3, 6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state2, m = step(state2, batch)
        resumed.append(float(m["loss"]))
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-5)


def test_hierarchical_step_names_its_collectives(tmp_path):
    """On four devices (pod 2 x data 2) the hierarchical step compiles to a
    module named jit_step, like the pjit step, and its collectives carry the
    grad_sync/{reduce_scatter,pod_allreduce,all_gather} scopes.  XLA_FLAGS
    must be set before jax is imported, hence the child process."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = tmp_path / "hier_scopes.py"
    script.write_text(
        """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models import get_api, smoke_config
from repro.train.optimizer import OptConfig
from repro.train.trainstep import TrainHparams, make_train_state, make_train_step
cfg = smoke_config("olmo-1b")
api = get_api(cfg)
mesh = make_mesh((2, 2, 1), ("pod", "data", "model"))
sds = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32) for k in ("tokens", "targets")}
hp = TrainHparams(hierarchical=True, zero1=True, compress=True)
step, _, _ = make_train_step(api, cfg, OptConfig(), mesh, hp, sds)
state = jax.eval_shape(lambda: make_train_state(api, jax.random.PRNGKey(0)))
print(step.lower(state, sds).compile().as_text())
"""
    )
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True, timeout=600, env=env
    )
    assert r.returncode == 0, r.stderr[-2000:]
    hlo = r.stdout
    assert hlo.startswith("HloModule jit_step,")
    for scope in ("grad_sync/reduce_scatter", "grad_sync/pod_allreduce", "grad_sync/all_gather"):
        assert scope + "/" in hlo, scope
    assert "optimizer/" in hlo


def test_run_train_marks_each_step_for_the_profiler(tmp_path):
    """Under the profiler, run_train leaves one ``train`` step annotation per
    step, numbered by the step, for XProf's step view."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import run_train

    cfg = smoke_config("olmo-1b")
    with jax.profiler.trace(str(tmp_path)):
        run_train(cfg, make_host_mesh(), steps=3, batch=2, seq=16, log_every=10)
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    steps = [
        dict(ev.stats)["step_num"]
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "train"
    ]
    assert steps == [0, 1, 2]
