"""Serving engine: greedy generation equals argmax of teacher-forced full
forward; batch independence."""
import jax
import numpy as np
import pytest

from repro.models import get_api, make_smoke_batch, smoke_config
from repro.serve.engine import ServeEngine


@pytest.mark.parametrize("arch", ["olmo-1b", "rwkv6-1.6b", "whisper-small"])
def test_greedy_matches_full_forward(arch):
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
