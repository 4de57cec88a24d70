"""The chip side of a run: finding the chips, the compile cache, memory.

Importing this module imports JAX but touches no device; the functions do.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax

from .spec import peaks_for, seed_words


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def find_chips(chips: int) -> List:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU, only {platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def chip_peaks(devices) -> Dict[str, float]:
    return peaks_for(devices[0].device_kind)


def enable_compile_cache() -> None:
    """Keep every compiled program in the program's persistent cache (its
    fixed path in the checkout, or ``JAX_COMPILATION_CACHE_DIR``), small
    ones too, so that only the first run of a cell in a checkout compiles."""
    from repro.launch.compile_cache import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts compilations (backend compiles and loads from the persistent
    cache) while ``counting`` is set: the measured window should have none."""

    _NAMES = ("backend_compile", "cache_hits", "cache_retrieval")

    def __init__(self):
        self.counting = False
        self.count = 0
        self.names: List[str] = []

        def on_event(name, *args, **kwargs):
            if self.counting and any(n in name for n in self._NAMES):
                self.count += 1
                self.names.append(name)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)


def weight_key(seed: int):
    """The PRNG key the weights are made from: every bit of the seed counts."""
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip, as the allocator reports it
    (None on a backend that keeps no such count, as the CPU of the tests)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    if None in peaks:
        return None
    return max(int(p) for p in peaks)


def free_device_memory() -> None:
    """Delete every array the process still holds, so the reference that
    follows has the chip to itself."""
    for a in jax.live_arrays():
        a.delete()
