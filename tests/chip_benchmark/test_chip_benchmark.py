"""Tests of the on-chip benchmark (``benchmarks/chip``), run on the CPU.

They cover what a run on the chip cannot show cheaply: that every file the
benchmark names is found, that a run without a TPU or on an unknown chip is
refused, the reduction of a trace to metrics, the FLOP count against the
compiled program's own dots, the plain reference against the program, and
that the check calls a run with its timed path broken ``correct: false``.
Sizes are tiny; nothing here is a measurement.
"""
from __future__ import annotations

import copy
import os
import subprocess
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

from harness import device, flops, spec  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.window import Run  # noqa: E402

CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
BIG_SEED = 2**33 + 12345  # past 32 bits, as the driver's seeds are

TINY_MODEL = dict(d_model=64, n_heads=4, n_layers=2, mlp_ratio=4, vocab_size=500, embedding_size=512)
TINY_PROGRAM = dict(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512
)


# what every family's module gives the harness (``harness/spec.py``)
FAMILY_FUNCTIONS = ("program_fields", "vocab", "train_step_flops", "prefill_flops", "decode_step_work")


def tiny_cell(workload: str, dtype: str = "bfloat16") -> spec.Cell:
    """The cell as BENCHMARK.json names it, at a size the CPU can hold."""
    cell = spec.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY_MODEL)
    cfg["assumed"]["dtype"] = dtype
    overrides = dict(TINY_PROGRAM, param_dtype=dtype, compute_dtype=dtype)
    cfg["program"] = {"arch": "olmo-1b", "overrides": overrides}
    cell.config = cfg
    t = dict(cell.traffic)
    if t["kind"] == "training":
        t.update(batch=4, seq=32)
    else:
        t.update(batch=4, prompt=24, new=6, check_requests=3, check_block=2)
    cell.traffic = t
    return cell


def tiny_run(cell, seed=BIG_SEED, seconds=0.5, devices=None) -> Run:
    devices = devices or jax.devices()[:1]
    return Run(cell, seed, seconds, False, devices, CPU_PEAKS, time.perf_counter())


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def test_every_file_is_found_by_name():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file(), c["file"]
        cfg = spec.load_json(ROOT / c["file"])
        assert (BENCH / "references" / f"{cfg['reference']}.py").is_file()
        family = spec.reference_module(cfg)
        for fn in FAMILY_FUNCTIONS:
            assert callable(getattr(family, fn, None)), (cfg["reference"], fn)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.kind in ("training", "serving")
        assert (BENCH / "harness" / f"{cell.kind}.py").is_file()
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert cell.limits, w["name"]
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for role in ("train_step", "prefill", "decode_step"):
        assert spec.program_patterns(role)


def test_program_config_matches_configuration_files():
    for w in spec.load_benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        run = tiny_run(cell)
        cfg = run.program_config()
        fields = run.reference().program_fields(cell.config)
        assert {k: getattr(cfg, k) for k in fields} == fields
        assert cfg.num_layers == cell.config["n_layers"]


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------

def test_no_tpu_is_refused_without_a_result(capsys):
    with pytest.raises(device.NoChip):
        device.find_chips(1)
    import run as runner

    assert runner.main(["--workload", "olmo-1b.decode", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_refused():
    assert spec.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v99")


def test_command_exits_nonzero_without_a_chip():
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "olmo-1b-l8.train",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]


def _recorded():
    """Two chips, two runs of a train step each, with a collective that the
    second chip overlaps half with compute.  Times in ns."""
    ms = 1_000_000
    mods = [(0, 10 * ms, "jit_step(17)"), (20 * ms, 30 * ms, "jit_step(17)")]
    d0 = [
        (0, 6 * ms, "fusion.1"), (6 * ms, 8 * ms, "all-reduce.3"), (8 * ms, 10 * ms, "fusion.2"),
        (20 * ms, 26 * ms, "fusion.1"), (26 * ms, 28 * ms, "all-reduce.3"), (28 * ms, 30 * ms, "fusion.2"),
    ]
    d1 = [
        (0, 7 * ms, "fusion.1"), (6 * ms, 8 * ms, "all-reduce-start.3"), (8 * ms, 10 * ms, "fusion.2"),
        (20 * ms, 27 * ms, "fusion.1"), (26 * ms, 28 * ms, "all-reduce-start.3"), (28 * ms, 30 * ms, "fusion.2"),
    ]
    host = [(9 * ms, 21 * ms, "bench.wait_lead_back"), (0, 40 * ms, "bench.window")]
    devs = [tr.DeviceTimeline("/device:TPU:0", d0, mods), tr.DeviceTimeline("/device:TPU:1", d1, mods)]
    return devs, host


def test_trace_reduction_on_a_recorded_trace():
    devs, host = _recorded()
    required = {"train_step": {"flops": 2e9}}
    patterns = {"train_step": [r"^jit_step\b"], "prefill": [r"^jit_prefill\b"]}
    view = tr.TraceView(devs, host, 0.040, required, CPU_PEAKS, patterns.get)
    assert view.busy_s() == pytest.approx(0.020)
    assert view.idle_share() == pytest.approx(50.0)
    assert view.program_ms("train_step") == pytest.approx(10.0)
    bd = view.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["idle_gaps"][0] == ["bench.wait_lead_back", pytest.approx(0.010)]
    assert spec.metric_reader("mfu.train")(view) == pytest.approx(100 * 2e9 / (0.010 * 1e12))
    assert spec.metric_reader("prefill_ms")(view) is None


def test_breakdown_counts_nested_ops_once():
    """A loop's ops lie inside the loop's own event: the breakdown gives each
    its own time, named by the HLO instruction's name alone."""
    ms = 1_000_000
    ops = [
        (0, 10 * ms, "%while.4 = (s32[], bf16[2,8]) while((s32[], bf16[2,8]) %tuple.1)"),
        (1 * ms, 4 * ms, "%fusion.7 = bf16[2,8] fusion(bf16[2,8] %p)"),
        (5 * ms, 9 * ms, "%copy.2 = bf16[2,8] copy(bf16[2,8] %q)"),
        (12 * ms, 13 * ms, "fusion.7"),
    ]
    assert tr.self_times(ops)[0] == (ops[0][2], 3 * ms)
    dev = tr.DeviceTimeline("/device:TPU:0", ops, [])
    view = tr.TraceView([dev], [], 0.020, {}, CPU_PEAKS, lambda role: [])
    assert view.breakdown()["device_ops"] == [
        ["fusion.7", pytest.approx(0.004)], ["copy.2", pytest.approx(0.004)], ["while.4", pytest.approx(0.003)]
    ]


def test_traced_run_fails_when_a_metric_reads_nothing():
    """The CPU's trace holds no TPU plane, so every per-layer reader of the
    cell finds nothing: the run raises instead of leaving the metrics out."""
    import run as runner

    run = tiny_run(tiny_cell("olmo-1b-l8.train"))
    run.trace = True
    with pytest.raises(spec.SpecError, match="read nothing"):
        runner.execute(run)


# ---------------------------------------------------------------------------
# the FLOP count against the compiled program's dots
# ---------------------------------------------------------------------------

def test_train_flops_match_the_compiled_dots():
    from repro.launch import hloparse
    from repro.launch.mesh import make_mesh
    from repro.models import get_api, smoke_config
    from repro.train.optimizer import OptConfig
    from repro.train.trainstep import TrainHparams, make_train_state, make_train_step

    cfg = smoke_config("olmo-1b").replace(num_kv_heads=4)
    api = get_api(cfg)
    B, S = 2, 32
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    sds = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "targets")}
    step, _, _ = make_train_step(api, cfg, OptConfig(), mesh, TrainHparams(), sds)
    state = jax.eval_shape(lambda: make_train_state(api, jax.random.PRNGKey(0)))
    counted = hloparse.analyze(step.lower(state, sds).compile().as_text()).flops
    # the OLMo configuration file of the smoke program's sizes
    config = copy.deepcopy(spec.load_cell("olmo-1b-l8.train").config)
    config.update(
        d_model=cfg.d_model, n_heads=cfg.num_heads, n_kv_heads=cfg.num_kv_heads,
        n_layers=cfg.num_layers, mlp_ratio=2 * cfg.d_ff // cfg.d_model,
        vocab_size=cfg.vocab_size, embedding_size=cfg.vocab_size,
    )
    family = spec.reference_module(config)
    assert family.sizes(config)["head_dim"] == cfg.head_dim
    required = family.train_step_flops(config, B, S)
    # the program computes every query-key pair and masks half of them away;
    # the required count takes the causal triangle only
    masked = 3 * cfg.num_layers * B * 4 * cfg.num_heads * cfg.head_dim * (S * S - S * (S + 1) // 2)
    assert counted == required + masked


def test_required_counts_of_the_cells():
    config = spec.load_cell("olmo-1b-l8.train").config
    family = spec.reference_module(config)
    assert family.layer_matmul_params(family.sizes(config)) == 4 * 2048**2 + 3 * 2048 * 8192
    assert family.train_step_flops(config, 4, 1024) == pytest.approx(16.14e12, rel=1e-3)
    c16 = spec.load_cell("olmo-1b.decode").config
    f, b = flops.generate_decode_mean(lambda context: family.decode_step_work(c16, 32, context), 512, 128)
    # weights once (2.35 GB) plus K and V at 513..639 filled positions
    assert b == pytest.approx(2 * (16 * 67108864 + 50304 * 2048) + 16 * 2 * 32 * 576 * 2048 * 2, rel=1e-6)
    assert f > 0


# ---------------------------------------------------------------------------
# the plain reference against the program
# ---------------------------------------------------------------------------

def _program(cell):
    from repro.models import get_api

    run = tiny_run(cell)
    cfg = run.program_config()
    return run, get_api(cfg)


def test_reference_weights_are_the_programs():
    cell = tiny_cell("olmo-1b.decode")
    run, api = _program(cell)
    ref = run.reference()
    prog = api.init(run.weight_key())
    mine = ref.make_weights(cell.config, BIG_SEED)
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        np.testing.assert_array_equal(np.asarray(leaf.astype(jnp.float32)), np.asarray(mine[name]))


def test_reference_matches_the_program_in_float32():
    cell = tiny_cell("olmo-1b.decode", dtype="float32")
    run, api = _program(cell)
    ref = run.reference()
    s = ref.sizes(cell.config)
    params = api.init(run.weight_key())
    w = ref.make_weights(cell.config, BIG_SEED)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 500, size=(2, 24)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        logits, _ = api.prefill(params, {"tokens": jnp.asarray(tokens)}, api.init_cache(2, 24))
        mine = ref.logits_at(s, "f32", w, tokens, np.arange(24))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(mine), atol=2e-4, rtol=2e-4)
        lp, gp = jax.value_and_grad(api.loss)(params, {"tokens": tokens, "targets": targets})
        lr, gr = jax.value_and_grad(lambda w: ref.loss(s, "f32", w, tokens, targets, chunk=8))(w)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(gr[name]), atol=1e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# runs with the timed path broken are not correct
# ---------------------------------------------------------------------------

def _execute(cell, break_timed_path=None, devices=None):
    import run as runner

    result, checks = runner.execute(tiny_run(cell, devices=devices), break_timed_path)
    return result


def _unchanged_state(step):
    def broken(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)  # the step donates its input
        _, metrics = step(state, batch)
        return kept, metrics

    return broken


def _half_batch(step):
    def broken(state, batch):
        h = batch["tokens"].shape[0] // 2
        half = {k: jnp.concatenate([v[:h], v[:h]]) for k, v in batch.items()}
        return step(state, half)

    return broken


@pytest.mark.parametrize("fault", [None, "unchanged_state", "half_batch"])
def test_training_check(fault):
    """The cell's limits, set for bfloat16 at the published widths, hold the
    tiny program in float32: at d_model 64 bfloat16's own rounding of the
    first gradient reads above the limit on ``grad_gap`` (6.6e-4)."""
    breaks = {None: None, "unchanged_state": _unchanged_state, "half_batch": _half_batch}
    result = _execute(tiny_cell("olmo-1b-l8.train", dtype="float32"), breaks[fault])
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_serving_check(fault):
    import calibrate

    result = _execute(tiny_cell("olmo-1b.decode"), calibrate.last_token_altered if fault else None)
    assert result["correct"] is (fault is None), result["checks"]


def reduced_cell(workload: str) -> spec.Cell:
    """The cell at its published widths with few layers and short sequences:
    the least size at which the cells' limits mean what they mean on the chip
    (at d_model 64 the logits are too small for an fp8 error to show, and
    with two layers too few products lie between the weights and a logit)."""
    cell = spec.load_cell(workload)
    t = dict(cell.traffic)
    if t["kind"] == "training":
        layers = 2
        t.update(batch=2, seq=128)
    else:
        layers = 4
        t.update(batch=2, prompt=32, new=8, check_requests=2, check_block=2)
    cfg = copy.deepcopy(cell.config)
    cfg["n_layers"] = layers
    cfg["program"] = {"arch": "olmo-1b", "overrides": {"num_layers": layers}}
    cell.config = cfg
    cell.traffic = t
    return cell


@pytest.mark.parametrize("workload", ["olmo-1b-l8.train", "olmo-1b.decode"])
def test_control_is_not_correct(workload):
    """The reference in fp8, put in the program's place, fails a limit that
    the bfloat16 program passes."""
    from harness import serving, training

    cell = reduced_cell(workload)
    run = tiny_run(cell, seed=7, seconds=0.1)
    if cell.kind == "serving":
        out = serving.run_cell(run)
        prog, ctrl = serving.check(run, out), serving.check(run, out, precision="fp8")
    else:
        out = training.run_cell(run)
        t = cell.traffic
        batches = training.rows_of(run.seed, t, run.token_vocab(), int(t["check_steps"]))
        ref_mod = run.reference()
        ref = ref_mod.train_readings(cell.config, run.seed, batches, t["optimizer"], run.devices)
        low = ref_mod.train_readings(
            cell.config, run.seed, batches, t["optimizer"], run.devices, precision="fp8"
        )
        prog, ctrl = training.compare(out["readings"], ref), training.compare(low, ref)
    assert all(prog[k] <= limit for k, limit in cell.limits.items()), prog
    assert any(ctrl[k] > limit for k, limit in cell.limits.items()), ctrl
