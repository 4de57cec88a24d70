"""Run one cell's window under the profiler and say where its device time went,
by the program's own scopes and host spans.

  python3 benchmarks/chip/scope_report.py --workload olmo-1b.decode --seed 7 --seconds 51

The window is the one ``run.py --trace 1`` traces (same set-up, traffic and
host loop); the run is not checked against the reference and reports no
benchmark result.  The last line of standard output is one JSON object:

* ``end_to_end``: the cell's host-clock metrics over the traced window, to
  set beside an untraced ``run.py`` run of the same seed (the cost of
  tracing);
* ``readings``: the per-layer readings of ``harness/scopes.py``
  (``READINGS``) that find something in this trace;
* per program (``train_step``, ``prefill``, ``decode_step``): device time
  per run, the time by innermost scope, the share of it on scoped ops and
  the unscoped ops that make up the rest;
* ``breakdown``: the top ops with their scope and the longest idle gaps by
  the innermost host span;
* serving cells: how far each decode run starts after the ``serve.decode``
  span that dispatched it.

The process compiles its programs afresh (the persistent compile cache is
left off) with XLA's dump of each optimized module on, so that an op the
trace names without its ``op_name`` is found in its module's text.
``--keep DIR`` also writes the trace there, gzipped, and the modules' texts.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# before JAX starts: XLA writes each compiled program's optimized text here
DUMP_DIR = tempfile.mkdtemp(prefix="scope-report-hlo-")
os.environ["XLA_FLAGS"] = " ".join(
    (os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={DUMP_DIR}", "--xla_dump_hlo_as_text",
     "--xla_dump_hlo_module_re=^jit_(step|prefill|decode_step)$")
).strip()

ROLES = ("train_step", "prefill", "decode_step")


def report(view) -> dict:
    from harness.scopes import READINGS

    out = {"readings": {}, "programs": {}}
    for name, read in READINGS.items():
        value = read(view)
        if value is not None:
            out["readings"][name] = value
    for role in ROLES:
        if not view.has_program(role):
            continue
        share, rest = view.coverage(role)
        out["programs"][role] = {
            "ms_per_run": view.program_ms(role),
            "runs": max(len(r) for r in view.runs(role)),
            "by_scope_ms": view.by_scope(role),
            "scoped_share": share,
            "unscoped_ops_ms": rest,
        }
    if view.has_program("decode_step"):
        leads = view.dispatch_leads("decode_step", "serve.decode")
        if leads:
            out["decode_dispatch_lead_ms"] = {
                "runs": len(leads),
                "min": min(leads) / 1e6,
                "max": max(leads) / 1e6,
                "negative": sum(1 for x in leads if x < 0),
            }
    out["breakdown"] = view.breakdown(top=15)
    return out


def module_texts(dump_dir: str) -> dict:
    """Module name -> optimized HLO text, the last one compiled of each."""
    out = {}
    for f in sorted(Path(dump_dir).glob("module_*.*after_optimizations.txt")):
        out[f.name.split(".")[1]] = f.read_text()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="directory to write the gzipped trace to")
    args = ap.parse_args(argv)

    import jax

    from harness import device
    from harness import scopes
    from harness.spec import SpecError, load_cell, program_patterns
    from harness.window import Run, log

    try:
        cell = load_cell(args.workload)
        devices = device.find_chips(cell.chips)
        peaks = device.chip_peaks(devices)
    except (device.NoChip, SpecError) as e:
        print(f"[scopes] {e}", file=sys.stderr)
        return 3
    # compile here, so that XLA dumps what runs (a cache hit dumps nothing)
    jax.config.update("jax_enable_compilation_cache", False)
    run = Run(cell, args.seed, args.seconds, True, devices, peaks, T_START)
    runner = importlib.import_module(f"harness.{cell.kind}")
    out = runner.run_cell(run)
    path = run.trace_file()
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(path, "rb") as f, gzip.open(Path(args.keep) / f"{cell.name}.{args.seed}.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
    texts = module_texts(DUMP_DIR)
    if args.keep:
        for module, text in texts.items():
            (Path(args.keep) / f"{cell.name}.{module}.hlo.txt").write_text(text)
    t0 = time.perf_counter()
    devs, host = scopes.read_xspace(path, texts)
    run.remove_trace()
    shutil.rmtree(DUMP_DIR, ignore_errors=True)
    view = scopes.ScopedView(devs, host, run.window_s, out["required"], run.peaks, program_patterns)
    result = {"workload": cell.name, "seed": args.seed, "end_to_end": out["end_to_end"], "window_s": run.window_s}
    result.update(report(view))
    log(f"reduced the trace in {time.perf_counter() - t0:.1f} s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
