"""Batched serving example: prefill + greedy KV-cache decode across three
architecture families (dense GQA, attention-free RWKV, encoder-decoder).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import numpy as np

from repro.models import get_api, smoke_config
from repro.serve.engine import ServeEngine

for arch in ("gemma-2b", "rwkv6-1.6b", "whisper-small"):
    cfg = smoke_config(arch)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S0, new = 4, 16, 12

    inputs = {"tokens": rng.integers(0, cfg.vocab_size, size=(B, S0)).astype(np.int32)}
    if cfg.family == "audio":
        inputs["frames"] = rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)).astype(
            np.float32
        )
    if cfg.family == "vlm":
        inputs["patches"] = rng.normal(
            size=(B, cfg.vision_tokens, cfg.vision_dim)
        ).astype(np.float32)

    eng = ServeEngine(api, params, batch=B, s_max=S0 + new + 4)
    t0 = time.perf_counter()
    out, _ = eng.generate(inputs, max_new_tokens=new)
    dt = time.perf_counter() - t0
    print(
        f"{arch:14s} generated {out.shape[0]}×{out.shape[1]} tokens "
        f"in {dt:5.2f}s ({B * new / dt:6.1f} tok/s)   first row: {out[0][:8].tolist()}"
    )
