"""Run one cell of the on-chip benchmark once, and print its result line.

  python3 benchmarks/chip/run.py --workload olmo-1b.decode --seed 7 --seconds 30 --trace 0

The cell, its configuration, its traffic mix and its metrics are named in
``BENCHMARK.json`` at the root of the checkout; their files live beside this
script (see ``harness/spec.py``).  The run makes its weights and inputs from
``--seed``, warms up every shape the window uses (set-up), measures for
``--seconds`` seconds, then checks what the timed path produced against a
plain float32 reference.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, read from the trace.  The last line of standard
output is one JSON object; the numbers compared with the reference, each with
its limit, are the last lines of standard error and the last key of that
object.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# the program under test
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
# the TPU runtime otherwise writes its logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(run, break_timed_path=None):
    """Set-up, window and check of one run; returns (result, checks)."""
    import importlib

    from harness import trace as tr
    from harness.spec import SpecError, metric_reader, program_patterns
    from harness.window import log

    runner = importlib.import_module(f"harness.{run.cell.kind}")
    out = runner.run_cell(run, break_timed_path)
    view = None
    if run.trace:
        devices, host = tr.read_xspace(run.trace_file())
        run.remove_trace()
        view = tr.TraceView(devices, host, run.window_s, out["required"], run.peaks, program_patterns)
    readings = runner.check(run, out)
    # the cell's file names the numbers it compares; the others are printed
    missing = set(run.cell.limits) - set(readings)
    if missing:
        raise SpecError(f"cells/{run.cell.name}.json limits {sorted(missing)}, which the check does not read")
    checks = {k: v for k, v in readings.items() if k in run.cell.limits}
    log(f"not compared {({k: v for k, v in readings.items() if k not in checks})}")
    correct = out["failed"] == 0 and all(
        math.isfinite(v) and v <= run.cell.limits[k] for k, v in checks.items()
    )
    dev = run.devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": run.memory_peak,
    }
    metrics = {}
    if view is None:
        e2e = dict(out["end_to_end"], setup_s=run.setup_s)
        for m in run.cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}
    else:
        for m in run.cell.per_layer:
            value = metric_reader(m.name)(view)
            if value is None:
                # the cell lists this metric, so its reader has to find it:
                # a name that the trace does not hold is a fault, not a gap
                raise SpecError(f"per-layer metric {m.name} read nothing in the trace of {run.cell.name}")
            metrics[m.name] = {"value": value, "unit": m.unit}
        device["busy_s"] = view.busy_s()
        device["window_s"] = run.window_s
    log(f"setup_s {run.setup_s} window_s {run.window_s} attempted {out['attempted']}")
    result = {
        "correct": bool(correct),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if view is not None:
        result["breakdown"] = view.breakdown()
    result["checks"] = {
        k: {"value": v, "limit": run.cell.limits[k]} for k, v in sorted(checks.items())
    }
    return result, checks


def main(argv=None) -> int:
    args = parse(argv)
    from harness.spec import SpecError, load_cell

    try:
        cell = load_cell(args.workload)
    except SpecError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2

    from harness import device
    from harness.window import Run

    try:
        devices = device.find_chips(cell.chips)
        peaks = device.chip_peaks(devices)
    except (device.NoChip, SpecError) as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache()
    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices, peaks, T_START)
    result, checks = execute(run)
    for k, v in sorted(checks.items()):
        print(f"check {k} {v!r} limit {cell.limits[k]!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
