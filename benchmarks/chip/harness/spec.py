"""What one run of the benchmark is: the cell, its files, its seed and its chips.

Everything that belongs to one configuration, traffic mix, cell, program or
per-layer metric lives in a file of its own under ``benchmarks/chip`` and is
found here by the name that ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   sizes of the model as run, with its source
* ``traffic/<traffic>.json``  the traffic mix, read by the runner its ``kind`` names
* ``cells/<workload>.json``   the limits that decide ``correct`` in that cell
* ``programs/<role>.json``    jit-name patterns of one program in the trace
* ``metrics/<metric>.py``     the reader of one per-layer metric
* ``references/<name>.py``    the family's module, named by a config's ``reference``

A family's module is the only reader of its configuration file's own keys;
the harness reads only ``name``, ``reference`` and ``program``.  Beside the
plain float32 reference (``make_weights``, ``served_gaps``,
``train_readings``) it gives what the sizes fix: ``program_fields``,
``vocab``, ``train_step_flops``, ``prefill_flops`` and ``decode_step_work``.

Nothing here imports JAX, so the files can be checked on any machine.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]

# the v5e peaks and any other chip's live in one table, keyed by device_kind
PEAKS_FILE = BENCH_DIR / "peaks.json"


class SpecError(Exception):
    """A file the benchmark needs is missing or says something inconsistent."""


def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_module(path: Path, name: str):
    """Import one file by path (metric readers and references are named by
    their benchmark names, which may hold dots and dashes)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip; an unknown kind is an error."""
    table = load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise SpecError(
            f"device_kind {device_kind!r} is not in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]] = None


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with every file it names, loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def _metrics(entries, keys) -> List[Metric]:
    return [Metric(**{k: e[k] for k in keys if k in e}) for e in entries]


def cell_metrics(bench: Dict[str, Any], workload: str):
    """(end-to-end, per-layer) metrics that ``workload`` reports.

    An end-to-end metric without ``workloads`` is reported everywhere; every
    per-layer metric lists the cells in which its reader finds something."""
    e2e = [
        m
        for m in _metrics(bench["end_to_end"], ("name", "unit", "workloads"))
        if m.workloads is None or workload in m.workloads
    ]
    for e in bench["per_layer"]:
        if "workloads" not in e:
            raise SpecError(f"per-layer metric {e['name']!r} lists no workloads")
    per_layer = [
        m for m in _metrics(bench["per_layer"], ("name", "unit", "workloads"))
        if workload in m.workloads
    ]
    return e2e, per_layer


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SpecError(f"unknown workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH_DIR / "cells" / f"{workload}.json")["limits"]
    e2e, per_layer = cell_metrics(bench, workload)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        traffic=traffic,
        limits={k: float(v["limit"]) for k, v in limits.items()},
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str):
    """The ``read(view)`` function of one per-layer metric."""
    mod = load_module(BENCH_DIR / "metrics" / f"{name}.py", f"metric_{name}")
    return mod.read


def program_patterns(role: str) -> List[str]:
    return load_json(BENCH_DIR / "programs" / f"{role}.json")["patterns"]


def reference_module(config: Dict[str, Any]):
    name = config["reference"]
    return load_module(BENCH_DIR / "references" / f"{name}.py", f"reference_{name}")


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

MASK32 = (1 << 32) - 1


def seed_words(seed: int) -> tuple:
    """Two 32-bit words that together hold any seed below 2**64.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a larger seed, so
    seeds 2**33 + 7 and 7 would give the same weights; the high word is
    folded into the key instead (see :func:`harness.device.weight_key`)."""
    if seed < 0 or seed >> 64:
        raise SpecError(f"seed {seed} is outside [0, 2**64)")
    return seed & MASK32, (seed >> 32) & MASK32


def host_rng(seed: int, stream: str):
    """A numpy generator for one named stream of the run's inputs."""
    import numpy as np

    lo, hi = seed_words(seed)
    tag = [ord(c) for c in stream]
    return np.random.default_rng([lo, hi, *tag])
