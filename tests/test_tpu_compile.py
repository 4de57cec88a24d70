"""Compiles for a described TPU v5e (nothing runs): the Pallas kernels at
the widths the models use, olmo-1b's decode and train steps at the sizes
``chip_smoke.py`` runs on one chip, and its serving steps at the benchmark's
serving sizes.  The chip's compiler refuses block
shapes that break its tiling and programs that do not fit its memory, which
interpret mode and the CPU backend never check.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and pytest workers
import every test file."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import flash_attention, rmsnorm, wkv6
from repro.launch.mesh import make_mesh
from repro.models import get_api
from repro.train.optimizer import OptConfig
from repro.train.trainstep import TrainHparams, make_train_state, make_train_step

# usable HBM of one v5e chip, as its compiler reports it ("15.75G", GiB)
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), tree
    )


def test_flash_attention_compiles_at_olmo_widths(one_chip):
    q = _sds((1, 16, 2048, 128), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, q, q, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    x = _sds((4096, 2048), jnp.bfloat16, one_chip)
    s = _sds((2048,), jnp.bfloat16, one_chip)
    compiled = rmsnorm.lower(x, s).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_compiles_at_rwkv6_widths(one_chip):
    B, H, T, K = 1, 32, 1024, 64
    x = _sds((B, H, T, K), jnp.bfloat16, one_chip)
    u = _sds((H, K), jnp.float32, one_chip)
    lw = _sds((B, H, T, K), jnp.float32, one_chip)
    s0 = _sds((B, H, K, K), jnp.float32, one_chip)
    compiled = wkv6.lower(x, x, x, lw, u, s0).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_olmo_decode_step_compiles_full_config(one_chip):
    """The serve phase's decode: all 16 layers, batch 8, 512 + 32 tokens."""
    api = get_api(configs.get_config("olmo-1b"))
    params = _on(jax.eval_shape(api.init, jax.random.PRNGKey(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: api.init_cache(8, 512 + 32 + 2)), one_chip)
    tok = _sds((8, 1), jnp.int32, one_chip)
    compiled = jax.jit(api.decode).lower(params, tok, cache).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 2e9


@pytest.mark.parametrize("batch, prompt, new", [(32, 512, 128), (8, 2016, 8)])
def test_olmo_serving_steps_update_the_cache_in_place(one_chip, batch, prompt, new):
    """The serving cells' prefill and decode, with the cache donated as
    ``ServeEngine`` donates it: the whole cache is aliased to the returned
    one and the chip holds no second copy of it, nor relays it out."""
    api = get_api(configs.get_config("olmo-1b"))
    params = _on(jax.eval_shape(api.init, jax.random.PRNGKey(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: api.init_cache(batch, prompt + new + 2)), one_chip)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
    inputs = {"tokens": _sds((batch, prompt), jnp.int32, one_chip)}
    tok = _sds((batch, 1), jnp.int32, one_chip)
    kv_shape = ",".join(map(str, cache["units"]["l0"][0].shape))
    for step, args in ((api.prefill, (params, inputs, cache)), (api.decode, (params, tok, cache))):
        compiled = jax.jit(step, donate_argnums=2).lower(*args).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= nbytes  # pos pads to one tile
        assert mem.temp_size_in_bytes < nbytes / 2, mem.temp_size_in_bytes
        assert not re.search(rf"bf16\[{kv_shape}\]\{{[^}}]*\}} copy\(", compiled.as_text())


def test_olmo_train_step_fits_one_chip_at_8_layers(topo):
    """The train phase's step: published widths, 8 of 16 layers, B=4, S=1024.
    All 16 layers do not fit: Adam's fp32 moments alone are 9.4 GB."""
    cfg = configs.get_config("olmo-1b").replace(num_layers=8)
    api = get_api(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    batch = {
        k: jax.ShapeDtypeStruct((4, 1024), jnp.int32) for k in ("tokens", "targets")
    }
    step, _, _ = make_train_step(api, cfg, OptConfig(), mesh, TrainHparams(), batch)
    state = jax.eval_shape(lambda: make_train_state(api, jax.random.PRNGKey(0)))
    mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.alias_size_in_bytes > 6e9  # the donated state is reused
    assert mem.peak_memory_in_bytes < V5E_HBM_BYTES, mem.peak_memory_in_bytes
