"""State of one run: its cell, seed and chips, the window, the trace."""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import jax

from . import device
from .spec import Cell, SpecError, reference_module


def leaf_paths(tree) -> List[str]:
    """'embed/tok', 'units/l0/mix/wq', ...: the program's names of its weights."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p) for p, _ in flat]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, devices, peaks, t_start: float):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.t_start = t_start
        self.peaks = peaks
        self.compiles = device.CompileCounter()
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.trace_dir: Optional[str] = None
        self.memory_peak: Optional[int] = None

    # ---- the program as the configuration states it ------------------------
    def program_config(self):
        """The program's ModelConfig for this cell, checked against the
        configuration file: a program that runs other sizes is refused."""
        # repro.models first: importing repro.configs alone is circular
        import repro.models  # noqa: F401
        from repro import configs

        c = self.cell.config
        prog = c["program"]
        cfg = configs.get_config(prog["arch"]).replace(**prog.get("overrides", {}))
        want = self.reference().program_fields(c)
        wrong = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
        if wrong:
            raise SpecError(f"the program's config differs from {c['name']}: {wrong}")
        return cfg

    def weight_key(self):
        return device.weight_key(self.seed)

    def token_vocab(self) -> int:
        """The range token ids are drawn from (the family's ``vocab``)."""
        return self.reference().vocab(self.cell.config)[0]

    def reference(self):
        """The family's module: the plain reference, and what the
        configuration's sizes fix for the harness."""
        return reference_module(self.cell.config)

    # ---- the window ---------------------------------------------------------
    def start_window(self) -> None:
        """End of set-up: from here nothing may compile.

        The objects that imports and set-up made are moved out of the
        collector's reach first: a full collection in the window would walk
        them all and stall the host loop for tens of milliseconds, at steps
        that differ from run to run.  What the window itself allocates is
        still collected."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.compiles.counting = True

    def end_window(self, window_s: float) -> None:
        self.compiles.counting = False
        self.window_s = window_s
        if self.trace:
            jax.profiler.stop_trace()
        log(f"compiles_in_window {self.compiles.count} {self.compiles.names}")

    def trace_file(self) -> Optional[Path]:
        if self.trace_dir is None:
            return None
        found = sorted(Path(self.trace_dir).rglob("*.xplane.pb"))
        return found[-1] if found else None

    def remove_trace(self) -> None:
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # ---- memory ---------------------------------------------------------------
    def read_memory_peak(self) -> None:
        self.memory_peak = device.memory_peak_bytes(self.devices)

    def note_memory_analysis(self, fn, *args) -> None:
        """Print the compiler's view of one program's memory beside the
        allocator's peak, to say which of the two tells whether it fits."""
        ma = fn.lower(*args).compile().memory_analysis()
        fields = (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "alias_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        )
        got = {f: int(getattr(ma, f, -1)) for f in fields}
        total = (
            got["argument_size_in_bytes"]
            + got["output_size_in_bytes"]
            - got["alias_size_in_bytes"]
            + got["temp_size_in_bytes"]
        )
        stats = self.devices[0].memory_stats() or {}
        log(
            f"memory_analysis {got} args+out-alias+temp {total} "
            f"allocator peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
            f"bytes_limit {stats.get('bytes_limit')}"
        )

    def free_program(self) -> None:
        device.free_device_memory()
