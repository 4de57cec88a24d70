"""Serving engine: greedy generation equals argmax of teacher-forced full
forward; batch independence."""
import jax
import numpy as np
import pytest

from repro.models import get_api, make_smoke_batch, smoke_config
from repro.serve.engine import ServeEngine


@pytest.mark.parametrize(
    "arch, calls",
    [
        pytest.param("olmo-1b", 1, id="olmo-1b"),
        pytest.param("rwkv6-1.6b", 1, id="rwkv6-1.6b"),
        pytest.param("whisper-small", 1, id="whisper-small"),
        # MLA, a dense prologue before the MoE units
        pytest.param("deepseek-v3-671b", 1, id="deepseek-v3-671b"),
        # attention and mamba in one unit
        pytest.param("jamba-1.5-large-398b", 1, id="jamba-1.5-large-398b"),
        # local and global attention alternate
        pytest.param("gemma2-9b", 1, id="gemma2-9b"),
        # a vision prefix in the cache
        pytest.param("internvl2-1b", 1, id="internvl2-1b"),
        # one engine, twice: a reused donated buffer raises
        pytest.param("olmo-1b", 2, id="olmo-1b-twice"),
    ],
)
def test_greedy_matches_full_forward(arch, calls):
    cfg = smoke_config(arch)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    B, S0, new = 2, 8, 6
    rng = np.random.default_rng(1)
    batch = make_smoke_batch(cfg, rng=rng, batch=B, seq=S0)
    inputs = {k: v for k, v in batch.items() if k != "targets"}

    eng = ServeEngine(api, params, batch=B, s_max=S0 + new + 2)
    out, _ = eng.generate(inputs, max_new_tokens=new)
    assert out.shape == (B, new)
    for _ in range(calls - 1):
        again, _ = eng.generate(inputs, max_new_tokens=new)
        np.testing.assert_array_equal(again, out)

    # oracle: extend token-by-token with full prefill each time
    import jax.numpy as jnp

    nv = cfg.vision_tokens if cfg.family == "vlm" else 0
    toks = np.asarray(batch["tokens"])
    for t in range(new):
        full = dict(inputs)
        full["tokens"] = jnp.asarray(toks)
        cache = api.init_cache(B, S0 + new + 2)
        logits, _ = api.prefill(params, full, cache)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
        np.testing.assert_array_equal(out[:, t], nxt, err_msg=f"{arch} tok {t}")
        toks = np.concatenate([toks, nxt[:, None]], axis=1)


def test_batch_slots_independent():
    """Each batch row decodes independently (no cross-slot leakage)."""
    cfg = smoke_config("olmo-1b")
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    b2 = make_smoke_batch(cfg, rng=rng, batch=2, seq=8)
    eng2 = ServeEngine(api, params, batch=2, s_max=20)
    out2, _ = eng2.generate({"tokens": b2["tokens"]}, max_new_tokens=4)
    for row in range(2):
        eng1 = ServeEngine(api, params, batch=1, s_max=20)
        out1, _ = eng1.generate({"tokens": b2["tokens"][row : row + 1]}, max_new_tokens=4)
        np.testing.assert_array_equal(out1[0], out2[row])


def test_generate_writes_its_spans(tmp_path):
    """Under the profiler, generate leaves serve.* host spans in the trace:
    one serve.decode and one serve.sample per new token after the first, the
    decode steps numbered in order, and every span of the call tagged with
    the same batch."""
    import collections

    cfg = smoke_config("olmo-1b")
    api = get_api(cfg)
    eng = ServeEngine(api, api.init(jax.random.PRNGKey(0)), batch=2, s_max=16)
    tokens = {"tokens": np.zeros((2, 8), np.int32)}
    new = 5
    eng.generate(tokens, new)  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(tokens, new)
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans[ev.name].append(dict(ev.stats))
    assert set(spans) == {"serve.setup", "serve.prefill", "serve.decode", "serve.sample", "serve.collect"}
    for name in ("serve.setup", "serve.prefill", "serve.collect"):
        assert len(spans[name]) == 1
    assert [s["step"] for s in spans["serve.decode"]] == list(range(new - 1))
    assert [s["step"] for s in spans["serve.sample"]] == list(range(new - 1))
    assert {s["batch"] for stats in spans.values() for s in stats} == {2}


def test_serving_steps_update_the_cache_in_place():
    """The compiled prefill and decode steps alias the whole donated cache to
    the cache they return, and hold no second copy of it: their temporaries
    are one layer's working set, the same at 4 layers as at 16.  (The xs/ys
    layer scan, donated, kept a whole copy: 502,560 temp bytes at 4 layers.)"""
    B, s_max = 4, 64
    inputs = {"tokens": np.zeros((B, 8), np.int32)}
    nbytes, temp = {}, {}
    for layers in (4, 16):
        api = get_api(smoke_config("olmo-1b").replace(num_layers=layers))
        eng = ServeEngine(api, jax.eval_shape(api.init, jax.random.PRNGKey(0)), batch=B, s_max=s_max)
        cache = jax.eval_shape(lambda: api.init_cache(B, s_max))
        nbytes[layers] = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
        assert eng.cache_alias_bytes(inputs) == {"prefill": nbytes[layers], "decode": nbytes[layers]}
        temp[layers] = {
            name: step.memory_analysis().temp_size_in_bytes
            for name, step in eng.compiled_steps(inputs).items()
        }
    assert nbytes[4] == 262_148
    assert max(temp[4].values()) < nbytes[4]
    assert temp[16] == temp[4]
    assert max(temp[16].values()) < nbytes[16] / 2
