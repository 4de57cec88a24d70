"""Checkpoint/restore: bitwise roundtrip, async write, latest-step pick,
elastic re-mesh restore (fault-tolerance contract)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.manager import latest_step, restore_checkpoint, save_checkpoint
from repro.launch.mesh import make_mesh


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {
            "w": jax.random.normal(k, (8, 16), jnp.float32),
            "b": jnp.arange(16, dtype=jnp.bfloat16),
        },
        "opt": {"m": jnp.zeros((8, 16)), "step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip_bitwise(tmp_path):
    state = _state()
    save_checkpoint(str(tmp_path), 7, state)
    restored = restore_checkpoint(str(tmp_path), jax.eval_shape(lambda: state))
    for a, b in zip(
        jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_write(tmp_path):
    t = save_checkpoint(str(tmp_path), 3, _state(), background=True)
    assert t is not None
    t.join(timeout=30)
    assert latest_step(str(tmp_path)) == 3


def test_latest_step_picks_max(tmp_path):
    for s in (1, 5, 3):
        save_checkpoint(str(tmp_path), s, _state(s))
    assert latest_step(str(tmp_path)) == 5
    restored = restore_checkpoint(str(tmp_path), jax.eval_shape(lambda: _state()))
    expect = _state(5)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(expect["params"]["w"])
    )


def test_restore_with_shardings(tmp_path):
    """Elastic restore: device_put with explicit (trivial 1-device) shardings."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    state = _state()
    save_checkpoint(str(tmp_path), 1, state)
    mesh = make_mesh((1, 1), ("data", "model"))
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: state)
    )
    restored = restore_checkpoint(
        str(tmp_path), jax.eval_shape(lambda: state), shardings=shardings
    )
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
    )


def test_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "nope"), {})
