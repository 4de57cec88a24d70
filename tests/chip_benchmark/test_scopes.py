"""Tests of the scope and span reduction (``benchmarks/chip/harness/scopes.py``)
on the CPU: on a small recorded trace with scopes and ``serve.*`` spans, on
the recorded trace of ``test_chip_benchmark.py``, and on the program's own
compiled text and host spans.  Nothing here is a measurement."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import scopes as sc  # noqa: E402
from harness import spec  # noqa: E402
from harness import trace as tr  # noqa: E402

CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
PATTERNS = {
    "train_step": [r"^jit_step\b"], "prefill": [r"^jit_prefill\b"], "decode_step": [r"^jit_decode_step\b"],
}
MS = 1_000_000


def test_scope_path_unwraps_autodiff_and_keeps_program_scopes():
    name = "jit(step)/transpose(jvp(layers))/while/body/closed_call/attn/jit(_where)/select_n"
    assert sc.scope_path(name) == ("layers", "attn")
    assert sc.scope_path("jit(decode_step)/layers/while/body/closed_call/attn/kv_cache/dynamic_update_slice") == (
        "layers", "attn", "kv_cache",
    )
    assert sc.scope_path("jit(step)/shard_map/optimizer/grad_sync/all_gather/all_gather") == (
        "optimizer", "grad_sync", "all_gather",
    )
    assert sc.scope_path("jit(f)/sin") == ()


def test_op_names_are_read_from_the_compiled_text():
    text = (
        "ENTRY %main {\n"
        '  %fusion.3 = f32[2] fusion(%p), kind=kLoop, metadata={op_name="jit(f)/layers/add"}\n'
        '  ROOT %copy.1 = f32[2] copy(%fusion.3)\n}'
    )
    assert sc.op_names_in(text) == {"fusion.3": "jit(f)/layers/add"}


def test_unscoped_ops_take_the_scope_of_the_loop_that_holds_them():
    ops = [(0, 10, "while.1"), (1, 2, "fusion.1"), (3, 4, "copy.1"), (11, 12, "copy.2")]
    paths = [("layers",), ("layers", "attn"), None, None]
    assert sc.inherit(ops, paths) == [("layers",), ("layers", "attn"), ("layers",), ()]


def _scoped_trace():
    """One prefill and two decode steps of one batch on one chip, with the
    program's scopes on the ops and its serve.* spans on the host.  The
    second op of each step's layer scan is a copy the compiler added, with
    no scope of its own; one op after the head has none either."""
    mods = [(2 * MS, 8 * MS, "jit_prefill(3)"), (10 * MS, 20 * MS, "jit_decode_step(4)"),
            (25 * MS, 35 * MS, "jit_decode_step(4)")]
    ops, paths = [(2 * MS, 8 * MS, "fusion.1")], [("layers", "attn")]
    for t in (10, 25):
        step = [
            ((t, t + 1), "embed_fusion", ("embed",)),
            ((t + 1, t + 8), "%while.3 = (s32[]) while(%tuple.2)", ("layers",)),
            ((t + 1, t + 2), "constant_dynamic-slice_fusion.10", ("layers",)),
            ((t + 2, t + 4), "fusion.5", ("layers", "attn")),
            ((t + 4, t + 5), "dynamic-update-slice.2", ("layers", "attn", "kv_cache")),
            ((t + 5, t + 6.5), "fusion.9", ("layers", "mlp")),
            ((t + 6.5, t + 7.5), "copy_bitcast_fusion.5", None),
            ((t + 8, t + 9.5), "fusion.20", ("head",)),
            ((t + 9.5, t + 10), "copy.7", None),
        ]
        ops += [(s * MS, e * MS, name) for (s, e), name, _ in step]
        paths += [p for _, _, p in step]
    dev = sc.ScopedTimeline("/device:TPU:0", ops, mods, sc.inherit(ops, paths))
    host = [
        (0, 40 * MS, "bench.generate"),
        (0, 2 * MS, "serve.setup"), (2 * MS, 3 * MS, "serve.prefill"),
        (9 * MS, int(10.5 * MS), "serve.decode"), (20 * MS, 23 * MS, "serve.sample"),
        (23 * MS, int(24.5 * MS), "serve.decode"), (35 * MS, 36 * MS, "serve.sample"),
        (36 * MS, 40 * MS, "serve.collect"),
    ]
    return sc.ScopedView([dev], host, 0.040, {}, CPU_PEAKS, PATTERNS.get)


def test_scoped_reduction_on_a_recorded_trace():
    view = _scoped_trace()
    # own time: the while's 0.5 ms, the slice's 1 ms and the copy's 1 ms
    assert view.scoped_ms("decode_step", "layers", exclusive=True) == pytest.approx(2.5)
    assert view.scoped_ms("decode_step", "kv_cache") == pytest.approx(1.0)
    assert view.scoped_ms("decode_step", "attn") == pytest.approx(3.0)
    assert view.scoped_ms("decode_step", "layers") == pytest.approx(7.0)
    assert view.scoped_ms("prefill", "attn") == pytest.approx(6.0)
    assert view.scoped_ms("train_step", "optimizer") is None
    assert view.by_scope("decode_step") == {
        "-": pytest.approx(0.5), "attn": pytest.approx(2.0), "embed": pytest.approx(1.0),
        "head": pytest.approx(1.5), "kv_cache": pytest.approx(1.0), "layers": pytest.approx(2.5),
        "mlp": pytest.approx(1.5),
    }
    share, rest = view.coverage("decode_step")
    assert share == pytest.approx(95.0)
    assert rest == [["copy.7", pytest.approx(0.5)]]
    # idle inside serve.decode and serve.sample: 1 + 3 + 1.5 + 1 ms, over 2 steps
    assert view.idle_ms_in(("serve.decode", "serve.sample"), per="serve.decode") == pytest.approx(3.25)
    # idle inside serve.setup and serve.collect: 2 + 4 ms, over 1 batch
    assert view.idle_ms_in(("serve.setup", "serve.collect"), per="serve.setup") == pytest.approx(6.0)
    assert view.idle_ms_in(("serve.decode",), per="train") is None
    assert [x / MS for x in view.dispatch_leads("decode_step", "serve.decode")] == [1.0, 2.0]
    readings = {k: read(view) for k, read in sc.READINGS.items()}
    assert readings == {
        "scan_io_ms.decode": pytest.approx(3.5), "attn_ms.prefill": pytest.approx(6.0),
        "scan_io_ms.train": None, "optimizer_ms.train": None,
        "token_gap_ms.serve": pytest.approx(3.25), "batch_gap_ms.serve": pytest.approx(6.0),
    }


def test_scoped_breakdown_names_ops_by_scope_and_gaps_by_program_span():
    bd = _scoped_trace().breakdown(top=3)
    assert bd["device_ops"][0] == ["fusion.1 [attn]", pytest.approx(0.006)]
    assert ["fusion.5 [attn]", pytest.approx(0.004)] in bd["device_ops"]
    # the gap between the two decode steps lies in serve.sample, not in the
    # benchmark's bench.generate around the whole call
    assert bd["idle_gaps"][0] == ["serve.sample", pytest.approx(0.005)]
    assert bd["idle_gaps"][1] == ["serve.decode", pytest.approx(0.002)]


def test_existing_readers_read_the_same_through_the_scoped_view():
    """The eight per-layer readers of BENCHMARK.json, and the breakdown,
    give the same values on the recorded trace of test_chip_benchmark.py
    whether it is read by TraceView or by ScopedView; a program without
    scopes reads None on every scoped reading."""
    from test_chip_benchmark import _recorded

    devs, host = _recorded()
    required = {"train_step": {"flops": 2e9}}
    plain = tr.TraceView(devs, host, 0.040, required, CPU_PEAKS, PATTERNS.get)
    scoped_devs = [sc.ScopedTimeline(d.name, d.ops, d.modules, [()] * len(d.ops)) for d in devs]
    scoped = sc.ScopedView(scoped_devs, host, 0.040, required, CPU_PEAKS, PATTERNS.get)
    names = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    assert len(names) == 8
    for name in names:
        read = spec.metric_reader(name)
        assert read(scoped) == read(plain), name
    assert scoped.breakdown()["idle_gaps"] == plain.breakdown()["idle_gaps"]
    assert [n.split(" [")[0] for n, _ in scoped.breakdown()["device_ops"]] == [
        n for n, _ in plain.breakdown()["device_ops"]
    ]
    assert all(read(scoped) is None for read in sc.READINGS.values())


def test_program_spans_are_read_from_a_cpu_trace(tmp_path):
    """The serve.* spans that ServeEngine.generate writes are among the host
    spans read_xspace keeps; the CPU trace has no TPU plane."""
    from repro.models import get_api, smoke_config
    from repro.serve.engine import ServeEngine

    cfg = smoke_config("olmo-1b")
    api = get_api(cfg)
    eng = ServeEngine(api, api.init(jax.random.PRNGKey(0)), batch=2, s_max=12)
    tokens = {"tokens": np.zeros((2, 6), np.int32)}
    eng.generate(tokens, 3)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.generate"):
            eng.generate(tokens, 3)
    devs, host = sc.read_xspace(sorted(tmp_path.rglob("*.xplane.pb"))[-1], {})
    assert devs == []
    names = sorted(name for _, _, name in host)
    assert names == sorted(
        ["bench.generate", "serve.setup", "serve.prefill", "serve.decode", "serve.decode",
         "serve.sample", "serve.sample", "serve.collect"]
    )


def test_ops_without_op_name_are_looked_up_in_their_modules_text():
    text = (
        "ENTRY %main {\n"
        '  %fusion.3 = f32[2] fusion(%p), kind=kLoop, metadata={op_name="jit(decode_step)/head/dot"}\n'
        "}"
    )
    by_module = {"jit_decode_step": sc.op_names_in(text)}
    modules = [(0, 10, "jit_prefill(1)"), (20, 30, "jit_decode_step(2)")]
    starts = [0, 20]
    assert sc.look_up(by_module, modules, starts, (21, 22, "%fusion.3 = f32[2] fusion(%p)")) == (
        "jit(decode_step)/head/dot"
    )
    assert sc.look_up(by_module, modules, starts, (2, 3, "fusion.3")) is None
    assert sc.look_up(by_module, modules, starts, (12, 13, "fusion.3")) is None
