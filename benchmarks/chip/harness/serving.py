"""Serving cells: ``ServeEngine.generate`` under a closed loop of batches.

One client hands the engine a batch of requests, waits for it to return, and
hands it the next.  A request's latency runs from the moment its batch is
handed to ``generate`` to the moment ``generate`` returns.  Prompts are
uniform token ids from the seed; every request asks for the same number of
new tokens, decoded greedily, so the check can read each served token.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import numpy as np

from . import flops
from .spec import host_rng
from .window import Run, log


class PromptFeed:
    def __init__(self, seed: int, stream: str, batch: int, prompt: int, vocab: int):
        self.rng = host_rng(seed, stream)
        self.shape = (batch, prompt)
        self.vocab = vocab

    def next(self) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=self.shape, dtype=np.int32)


def build(run: Run, break_engine=None):
    from repro.models import get_api
    from repro.serve.engine import ServeEngine

    t = run.cell.traffic
    cfg = run.program_config()
    api = get_api(cfg)
    dev = run.devices[0]
    with jax.default_device(dev):
        params = jax.jit(api.init)(run.weight_key())
    eng = ServeEngine(api, params, batch=t["batch"], s_max=t["prompt"] + t["new"] + t["s_max_extra"])
    if break_engine is not None:
        eng = break_engine(eng)
    return eng


def run_cell(run: Run, break_engine=None) -> Dict:
    t = run.cell.traffic
    B, Pn, N = t["batch"], t["prompt"], t["new"]
    family = run.reference()
    vocab, served_vocab = family.vocab(run.cell.config)
    with jax.default_device(run.devices[0]):
        eng = build(run, break_engine)
        # warm every shape the window uses: one generate of the cell's size
        eng.generate({"tokens": PromptFeed(run.seed, "warm-up", B, Pn, vocab).next()}, N)
        feed = PromptFeed(run.seed, "prompts", B, Pn, vocab)
        prompts, served, latencies = [], [], []
        run.start_window()
        t0 = time.perf_counter()
        t_end = t0
        while t_end - t0 < run.seconds:
            batch = feed.next()
            t_hand = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.generate"):
                tokens, _ = eng.generate({"tokens": batch}, N)
            t_end = time.perf_counter()
            latencies.extend([t_end - t_hand] * B)
            prompts.append(batch)
            served.append(np.asarray(tokens))
        run.end_window(t_end - t0)
        log(f"batch_s {latencies[::B]}")
        run.read_memory_peak()
    del eng
    run.free_program()

    served_all = np.concatenate(served, axis=0)
    bad = ~((served_all >= 0) & (served_all < served_vocab)).all(axis=1)
    dec_f, dec_b = flops.generate_decode_mean(
        lambda context: family.decode_step_work(run.cell.config, B, context), Pn, N
    )
    n_req = len(latencies)
    return {
        "attempted": n_req,
        "failed": int(bad.sum()),
        "end_to_end": {
            "gen_tok_s": n_req * N / (t_end - t0),
            "request_p95_s": float(np.percentile(np.asarray(latencies), 95)),
        },
        "required": {
            "prefill": {"flops": family.prefill_flops(run.cell.config, B, Pn)},
            "decode_step": {"flops": dec_f, "bytes": dec_b},
        },
        "prompts": np.concatenate(prompts, axis=0),
        "served": served_all,
    }


def sample(run: Run, n_requests: int) -> np.ndarray:
    """Which finished requests the check reads, drawn from the seed.  Every
    request of a cell serves the same number of tokens, so each is among
    the longest."""
    k = int(run.cell.traffic["check_requests"])
    rng = host_rng(run.seed, "check-sample")
    return np.sort(rng.choice(n_requests, size=min(k, n_requests), replace=False))


def check(run: Run, out: Dict, precision: str = "f32") -> Dict[str, float]:
    idx = sample(run, len(out["served"]))
    gaps = run.reference().served_gaps(
        run.cell.config,
        run.seed,
        out["prompts"][idx],
        out["served"][idx],
        block=int(run.cell.traffic["check_block"]),
        precision=precision,
    )
    return {"logit_gap": float(np.max(gaps))}
