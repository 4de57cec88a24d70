"""Readings from which a cell's limits are set, over many seeds in one process.

  python3 benchmarks/chip/calibrate.py --workload olmo-1b-l8.train --seeds 11,12,13 --seconds 2

For every seed it runs the cell as ``run.py`` does, with a short window, and
prints one JSON line with the numbers the check compares:

* ``program``: the program against the float32 reference (the lower reading);
* ``control``: the reference computed in fp8, put in the program's place
  (the upper reading);
* ``faults``: faults planted in the program's place, each against the
  reference.  Training: ``half_batch`` (half the rows left out, the mean
  taken over the rest).  Serving:
  ``token_altered`` (each request's last token replaced by the one its
  logits rank last).  A step that returns its state unchanged reads 1 on
  ``update_gap`` by that number's definition and needs no run.

The last line sums up: the largest program reading and the smallest control
and fault readings of every number.  Limits are set between the two by hand
and written into ``cells/<workload>.json`` with the readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# the program under test
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def half_rows(batches):
    """Half of each batch left out: the first half of the rows, twice, so the
    mean is taken over that half alone."""
    import numpy as np

    out = []
    for tokens, targets in batches:
        h = tokens.shape[0] // 2
        out.append((np.concatenate([tokens[:h]] * 2), np.concatenate([targets[:h]] * 2)))
    return out


def last_token_altered(engine):
    """Plant a fault in the program: the last token of every request becomes
    the one the engine's own last logits rank lowest."""
    import numpy as np

    generate = engine.generate

    def broken(batch, n):
        tokens, logits = generate(batch, n)
        tokens = np.array(tokens)
        tokens[:, -1] = np.argmin(logits, axis=-1)
        return tokens, logits

    engine.generate = broken
    return engine


def calibrate_seed(run, faults: bool):
    from harness import serving, training
    from harness.window import Run

    cell = run.cell
    if cell.kind == "training":
        out = training.run_cell(run)
        t = cell.traffic
        ref_mod = run.reference()
        batches = training.rows_of(run.seed, t, run.token_vocab(), int(t["check_steps"]))
        ref = ref_mod.train_readings(cell.config, run.seed, batches, t["optimizer"], run.devices)
        line = {"program": training.compare(out["readings"], ref)}
        ctrl = ref_mod.train_readings(
            cell.config, run.seed, batches, t["optimizer"], run.devices, precision="fp8"
        )
        line["control"] = training.compare(ctrl, ref)
        if faults:
            half = ref_mod.train_readings(
                cell.config, run.seed, half_rows(batches), t["optimizer"], run.devices
            )
            line["faults"] = {"half_batch": training.compare(half, ref)}
        return line
    out = serving.run_cell(run)
    line = {
        "program": serving.check(run, out),
        "control": serving.check(run, out, precision="fp8"),
    }
    if faults:
        again = Run(cell, run.seed, run.seconds, False, run.devices, run.peaks, time.perf_counter())
        bad = serving.run_cell(again, last_token_altered)
        line["faults"] = {"token_altered": serving.check(again, bad)}
    line["requests"] = int(len(out["served"]))
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()

    from harness import device
    from harness.spec import load_cell
    from harness.window import Run

    cell = load_cell(args.workload)
    devices = device.find_chips(cell.chips)
    peaks = device.chip_peaks(devices)
    device.enable_compile_cache()
    lines = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = Run(cell, seed, args.seconds, False, devices, peaks, t0)
        line = calibrate_seed(run, args.faults and i < 3)
        line["seed"] = seed
        line["seconds"] = time.perf_counter() - t0
        lines.append(line)
        print(json.dumps(line), flush=True)
    summary = {"largest_program": {}, "smallest_control": {}, "smallest_fault": {}}
    for line in lines:
        for k, v in line["program"].items():
            summary["largest_program"][k] = max(v, summary["largest_program"].get(k, v))
        for k, v in line["control"].items():
            summary["smallest_control"][k] = min(v, summary["smallest_control"].get(k, v))
        for f, nums in line.get("faults", {}).items():
            for k, v in nums.items():
                key = f"{f}.{k}"
                summary["smallest_fault"][key] = min(v, summary["smallest_fault"].get(key, v))
    print(json.dumps({"summary": summary, "seeds": len(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
