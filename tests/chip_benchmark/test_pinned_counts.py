"""What the benchmark's three cells take from their configuration files,
pinned to the numbers the cells have been measured with.

The program's fields that a run checks, the two ranges of token ids, and the
work each runner hands the per-layer metrics as ``required`` (from which the
``mfu.*`` shares are read).  The runners are driven through their own
``run_cell`` with the program replaced by a stand-in that does no work, so
the cells' full sizes cost nothing here.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import serving, spec, training  # noqa: E402
from harness.window import Run  # noqa: E402

CELLS = ["olmo-1b-l8.train", "olmo-1b.decode", "olmo-1b.prefill"]

OLMO_1B_FIELDS = dict(
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=8192,
    vocab_size=50304,
    tie_embeddings=True,
    norm_kind="nonparametric",
    mlp_kind="swiglu",
    rope_theta=10000.0,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)
PROGRAM_FIELDS = {
    "olmo-1b": OLMO_1B_FIELDS,
    "olmo-1b-l8": dict(OLMO_1B_FIELDS, num_layers=8),
}
# a value other than the pinned one, for each field
OTHER = dict(
    num_layers=3,
    d_model=1024,
    num_heads=8,
    num_kv_heads=4,
    d_ff=4096,
    vocab_size=50280,
    tie_embeddings=False,
    norm_kind="layernorm",
    mlp_kind="gelu",
    rope_theta=500000.0,
    param_dtype="float32",
    compute_dtype="float32",
)

# token ids are drawn below the first, served ids must lie below the second
VOCAB = (50280, 50304)

REQUIRED = {
    "olmo-1b-l8.train": {"train_step": {"flops": 16138742267904.0}},
    "olmo-1b.decode": {
        "prefill": {"flops": 35741795090432.0},
        "decode_step": {"flops": 77728841728.0, "bytes": 4769447936.0},
    },
    "olmo-1b.prefill": {
        "prefill": {"flops": 36768162250752.0},
        "decode_step": {"flops": 20946354176.0, "bytes": 4471652352.0},
    },
}


def make_run(cell) -> Run:
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    return Run(cell, 2**33 + 5, 0.05, False, jax.devices()[:1], peaks, time.perf_counter())


def with_overrides(cell, **overrides):
    cell = dataclasses.replace(cell, config=dict(cell.config))
    prog = cell.config["program"]
    cell.config["program"] = dict(prog, overrides=dict(prog.get("overrides", {}), **overrides))
    return cell


@pytest.mark.parametrize("workload", CELLS)
def test_program_fields_of_the_cells(workload):
    """The program's config carries the pinned fields, a change to any one
    of them is refused, and a change to any other field is not checked."""
    from repro.models.config import ModelConfig

    cell = spec.load_cell(workload)
    want = PROGRAM_FIELDS[cell.config_name]
    cfg = make_run(cell).program_config()
    assert {k: getattr(cfg, k) for k in want} == want
    for k in want:
        with pytest.raises(spec.SpecError):
            make_run(with_overrides(cell, **{k: OTHER[k]})).program_config()
    unchecked = object()
    for f in dataclasses.fields(ModelConfig):
        if f.name not in want:
            make_run(with_overrides(cell, **{f.name: unchecked})).program_config()


class FakeEngine:
    """Serves row 0 of every batch at the top id of the served range and
    row 1 one past it: one failed request a batch."""

    def __init__(self, batch: int, new: int, top: int):
        self.tokens = np.zeros((batch, new), np.int32)
        self.tokens[0, :] = top - 1
        self.tokens[1, -1] = top

    def generate(self, batch, n):
        assert batch["tokens"].shape[0] == self.tokens.shape[0] and n == self.tokens.shape[1]
        return self.tokens, None


def fake_train_build(run, break_step=None):
    params = {"w": jnp.ones((2,), jnp.float32)}
    state = {"params": params, "opt": {"m": params}}

    def step(state, batch):
        return state, {"loss": jnp.zeros((), jnp.float32)}

    return step, state, jax.devices()[0], lambda k: {"w": jnp.zeros((2,), jnp.float32)}, None


@pytest.mark.parametrize("workload", CELLS)
def test_vocab_and_required_counts_of_the_cells(workload, monkeypatch):
    cell = spec.load_cell(workload)
    run = make_run(cell)
    drawn = []
    if cell.kind == "serving":
        t = cell.traffic

        class Feed(serving.PromptFeed):
            def __init__(self, seed, stream, batch, prompt, vocab):
                drawn.append(vocab)
                super().__init__(seed, stream, batch, prompt, vocab)

        monkeypatch.setattr(serving, "PromptFeed", Feed)
        monkeypatch.setattr(
            serving, "build", lambda run, break_engine=None: FakeEngine(t["batch"], t["new"], VOCAB[1])
        )
        out = serving.run_cell(run)
        assert out["failed"] == out["attempted"] // t["batch"] > 0
    else:

        class Feed(training.TrainFeed):
            def __init__(self, seed, batch, seq, vocab):
                drawn.append(vocab)
                super().__init__(seed, batch, seq, vocab)

        monkeypatch.setattr(training, "TrainFeed", Feed)
        monkeypatch.setattr(training, "build", fake_train_build)
        out = training.run_cell(run)
        assert out["failed"] == 0
    assert drawn and set(drawn) == {VOCAB[0]}
    assert run.token_vocab() == VOCAB[0]
    assert out["required"] == REQUIRED[workload]
