"""Head-padding (§Perf iteration 2): the padded attention path must be
*exactly* equivalent to the unpadded path — padded q slots are zeros and
their outputs are sliced away before the output projection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import shard_hints
from repro.models.attention import _head_pad_plan, gqa_attention
from repro.models.config import ModelConfig


@pytest.fixture(autouse=True)
def _reset_sizes():
    yield
    shard_hints._set_sizes_for_test({})
    shard_hints.use_hints(None)


@pytest.mark.parametrize(
    "hq,hkv,m,expect",
    [
        (40, 8, 16, (2, 16, 3, 48)),   # qwen2.5
        (48, 8, 16, (2, 16, 3, 48)),   # grok
        (64, 8, 16, (2, 16, 4, 64)),   # jamba
        (16, 8, 16, (2, 16, 1, 16)),   # gemma2
        (14, 2, 16, (8, 16, 1, 16)),   # internvl2
        (16, 16, 16, None),            # already divisible
        (8, 1, 16, None),              # gemma-2b: 2× waste → rejected
        (12, 12, 16, None),            # whisper: 4× waste → rejected
    ],
)
def test_pad_plan(hq, hkv, m, expect):
    shard_hints._set_sizes_for_test({"model": m})
    plan = _head_pad_plan(hq, hkv)
    if expect is None:
        assert plan is None
        return
    r, hkv_p, g_p, hq_p, perm, inv = plan
    assert (r, hkv_p, g_p, hq_p) == expect
    perm = np.asarray(perm)
    inv = np.asarray(inv)
    # every original head appears exactly once, at the slot inv points to
    orig = perm[perm >= 0]
    assert sorted(orig.tolist()) == list(range(hq))
    for h in range(hq):
        assert perm[inv[h]] == h
    # group consistency: padded slot s uses kv_p[s // g_p] = kv[(s//g_p)//r],
    # which must equal the original head's kv group perm[s] // (hq//hkv)
    g = hq // hkv
    for s, o in enumerate(perm):
        if o >= 0:
            assert (s // g_p) // r == o // g


@pytest.mark.parametrize("hq,hkv,m", [(40, 8, 16), (14, 2, 16), (64, 8, 16)])
def test_padded_attention_exact(hq, hkv, m):
    """gqa_attention with the padding plan active equals the plain path."""
    cfg = ModelConfig(
        name="t", family="dense", num_layers=1, d_model=hq * 16,
        num_heads=hq, num_kv_heads=hkv, d_ff=64, vocab_size=64, head_dim=16,
        param_dtype="float32", compute_dtype="float32",
    )
    params = jax.vmap(lambda k: None)  # placeholder
    from repro.models.attention import init_gqa

    p = init_gqa(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 24, cfg.d_model)).astype(np.float32)
    )
    base, _ = gqa_attention(p, x, cfg)

    shard_hints._set_sizes_for_test({"model": m})
    # make active() true without a real mesh: register the host mesh but
    # keep the test sizes (model=m) for the planner
    mesh = make_mesh((1, 1), ("data", "model"))
    shard_hints.use_hints(mesh)
    shard_hints._set_sizes_for_test({"model": m, "data": 1})
    padded, _ = gqa_attention(p, x, cfg)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(padded), atol=2e-5, rtol=2e-5
    )
