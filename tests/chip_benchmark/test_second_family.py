"""A configuration of another family runs through both runners.

The registered RWKV-6 smoke program, described by a configuration file in
RWKV's own key names (those of the published ``config.json``), none of
which the OLMo files use besides ``vocab_size``.  Its family module is a
stand-in local to this file: it states the program's fields, the two ranges
of token ids and a count of the work, and the runners are held to take each
of them from it.  A runner that read a key of OLMo's files would fail here
with ``KeyError``.  Nothing is checked against a reference: RWKV has none yet.
"""
from __future__ import annotations

import dataclasses
import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from harness import serving, spec, training  # noqa: E402
from harness.window import Run  # noqa: E402


def rwkv_smoke_config():
    """The smoke program's sizes in RWKV-6's published key names; the
    program is the registered full config with the smoke sizes as overrides."""
    from repro.configs import get_config
    from repro.models import smoke_config

    full, smoke = get_config("rwkv6-1.6b"), smoke_config("rwkv6-1.6b")
    overrides = {
        f.name: getattr(smoke, f.name)
        for f in dataclasses.fields(smoke)
        if getattr(smoke, f.name) != getattr(full, f.name)
    }
    return {
        "name": "rwkv6-1.6b-smoke",
        "reference": "rwkv6",
        "program": {"arch": "rwkv6-1.6b", "overrides": overrides},
        "hidden_size": smoke.d_model,
        "attention_hidden_size": smoke.num_heads * smoke.head_dim,
        "head_size": smoke.rwkv.head_dim,
        "intermediate_size": smoke.d_ff,
        "num_hidden_layers": smoke.num_layers,
        "vocab_size": smoke.vocab_size,
        "tie_word_embeddings": smoke.tie_embeddings,
        "layer_norm_epsilon": 1e-5,
        "torch_dtype": smoke.param_dtype,
    }


# ---------------------------------------------------------------------------
# the family's module, as references/<name>.py would give it
# ---------------------------------------------------------------------------

def _sizes(config):
    d, hs = config["hidden_size"], config["head_size"]
    # time mix: r, k, v, g, o; channel mix: key, value, receptance
    per_layer = 5 * d * d + 2 * d * config["intermediate_size"] + d * d
    return {
        "d": d,
        "layers": config["num_hidden_layers"],
        "matmul": per_layer,
        "head": d * config["vocab_size"],
        # the WKV recurrence per token and layer: a head_size^2 state per head
        "wkv": 4 * d * hs,
        "state_bytes": config["num_hidden_layers"] * d * hs * 4,
    }


def program_fields(config):
    return {
        "num_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "num_heads": config["attention_hidden_size"] // config["head_size"],
        "head_dim": config["head_size"],
        "d_ff": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "tie_embeddings": config["tie_word_embeddings"],
        "attn_kind": "none",
        "norm_kind": "layernorm",
        "param_dtype": config["torch_dtype"],
        "compute_dtype": config["torch_dtype"],
    }


def vocab(config):
    # ids from a slice below the embedding's rows, so that a runner that
    # read ``vocab_size`` itself would draw from the wrong range
    return config["vocab_size"] - 12, config["vocab_size"]


def train_step_flops(config, batch, seq):
    s = _sizes(config)
    tokens = batch * seq
    return 3.0 * (2 * tokens * (s["layers"] * s["matmul"] + s["head"]) + tokens * s["layers"] * s["wkv"])


def prefill_flops(config, batch, prompt):
    s = _sizes(config)
    tokens = batch * prompt
    return float(2 * tokens * s["layers"] * s["matmul"] + tokens * s["layers"] * s["wkv"] + 2 * batch * s["head"])


def decode_step_work(config, batch, context):
    """No cache grows with the context: the recurrent state is read instead."""
    s = _sizes(config)
    flops = 2 * batch * (s["layers"] * s["matmul"] + s["head"]) + batch * s["layers"] * s["wkv"]
    weight_bytes = (s["layers"] * s["matmul"] + s["head"]) * 4
    return float(flops), float(weight_bytes + batch * s["state_bytes"])


FAMILY = types.SimpleNamespace(
    program_fields=program_fields,
    vocab=vocab,
    train_step_flops=train_step_flops,
    prefill_flops=prefill_flops,
    decode_step_work=decode_step_work,
)


# ---------------------------------------------------------------------------
# both runners
# ---------------------------------------------------------------------------

def rwkv_cell(workload: str) -> spec.Cell:
    """The OLMo cell's traffic kind, shrunk, with the RWKV configuration."""
    cell = spec.load_cell(workload)
    t = dict(cell.traffic)
    if t["kind"] == "training":
        t.update(batch=2, seq=16)
    else:
        t.update(batch=2, prompt=8, new=4)
    return dataclasses.replace(cell, config=rwkv_smoke_config(), traffic=t, limits={})


def test_the_configuration_has_none_of_olmos_keys():
    olmo = spec.load_cell("olmo-1b.decode").config
    assert set(rwkv_smoke_config()) & set(olmo) == {"name", "reference", "program", "vocab_size"}


@pytest.mark.parametrize("workload", ["olmo-1b-l8.train", "olmo-1b.decode"])
def test_second_family_runs_through_the_runner(workload, monkeypatch):
    monkeypatch.setattr(Run, "reference", lambda self: FAMILY)
    cell = rwkv_cell(workload)
    config, t = cell.config, cell.traffic
    run = Run(cell, 2**33 + 3, 0.2, False, jax.devices()[:1], {}, time.perf_counter())
    assert run.program_config().attn_kind == "none"
    assert run.token_vocab() == vocab(config)[0]
    if cell.kind == "serving":
        out = serving.run_cell(run)
        assert out["prompts"].max() < vocab(config)[0]
        assert out["served"].shape == (out["attempted"], t["new"])
        assert out["required"] == {
            "prefill": {"flops": prefill_flops(config, t["batch"], t["prompt"])},
            "decode_step": dict(zip(("flops", "bytes"), decode_step_work(config, t["batch"], t["prompt"] + 1))),
        }
    else:
        out = training.run_cell(run)
        assert len(out["readings"]["losses"]) == t["check_steps"]
        assert out["required"] == {"train_step": {"flops": train_step_flops(config, t["batch"], t["seq"])}}
    assert out["attempted"] > 0 and out["failed"] == 0
