"""Batched serving driver: prefill + greedy KV-cache decode.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --batch 4 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from .. import configs
from ..models import get_api, smoke_config
from ..models.config import ModelConfig
from ..serve.engine import ServeEngine
from .compile_cache import enable_compile_cache


def run_serve(
    cfg: ModelConfig, *, batch: int, prompt_len: int, max_new: int
) -> dict:
    """Serve ``batch`` random prompts with random weights, both from seed 0.

    One warm-up ``generate`` compiles prefill and decode; the second is
    timed.  Returns the engine, the inputs, the new tokens, the last step's
    logits and the two wall times."""
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    inputs = {
        "tokens": rng.integers(
            0, cfg.vocab_size, size=(batch, prompt_len)
        ).astype(np.int32)
    }
    if cfg.family == "audio":
        inputs["frames"] = rng.normal(
            size=(batch, cfg.encoder_seq, cfg.d_model)
        ).astype(np.float32)
    if cfg.family == "vlm":
        inputs["patches"] = rng.normal(
            size=(batch, cfg.vision_tokens, cfg.vision_dim)
        ).astype(np.float32)

    s_max = prompt_len + max_new + (
        cfg.vision_tokens if cfg.family == "vlm" else 0
    ) + 2
    eng = ServeEngine(api, params, batch=batch, s_max=s_max)

    t0 = time.perf_counter()
    eng.generate(inputs, max_new_tokens=max_new)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tokens, last_logits = eng.generate(inputs, max_new_tokens=max_new)
    generate_s = time.perf_counter() - t0
    return {
        "engine": eng,
        "inputs": inputs,
        "tokens": tokens,
        "last_logits": last_logits,
        "first_call_s": first_s,
        "generate_s": generate_s,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a profiler trace of the data plane here (XProf / TensorBoard)",
    )
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    profile = jax.profiler.trace(args.profile_dir) if args.profile_dir else contextlib.nullcontext()
    with profile:
        res = run_serve(
            cfg, batch=args.batch, prompt_len=args.prompt_len, max_new=args.max_new
        )
    out, dt = res["tokens"], res["generate_s"]
    toks = args.batch * args.max_new
    print(f"first call (compile + run) {res['first_call_s']:.2f}s")
    print(f"generated {out.shape} in {dt:.2f}s → {toks/dt:,.1f} tok/s")
    print("first row:", out[0][:12].tolist())


if __name__ == "__main__":
    main()
