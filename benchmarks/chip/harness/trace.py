"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an XSpace (``*.xplane.pb``).  On a TPU each chip is
a plane named ``/device:TPU:<n>``; its line ``XLA Modules`` holds one event
per run of a compiled program (named ``jit_<function>(<id>)``) and its line
``XLA Ops`` one event per operation.  The host's planes hold the
benchmark's own ``TraceAnnotation`` spans (``bench.*``).

Everything here works on plain (start, end, name) tuples in nanoseconds, so
the tests can feed it a small recorded trace.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[float, float, str]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted ``a`` that no interval of the disjoint
    sorted ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between the busy ones."""
    return subtract([(lo, hi)], busy)


def op_name(name: str) -> str:
    """'while.4' of '%while.4 = (s32[], ...) while(...)': the trace names an
    operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: Iterable[Event]) -> List[Tuple[str, float]]:
    """Each event's time less that of the events nested in it: a loop's ops
    lie inside the loop's own event on the same line."""
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    own = [e - s for s, e, _ in order]
    open_: List[Tuple[float, int]] = []
    for i, (s, e, _) in enumerate(order):
        while open_ and open_[-1][0] <= s:
            open_.pop()
        if open_:
            own[open_[-1][1]] -= min(e, open_[-1][0]) - s
        open_.append((e, i))
    return [(name, t) for (_, _, name), t in zip(order, own)]


class DeviceTimeline:
    """One chip's operations and program runs."""

    def __init__(self, name: str, ops: List[Event], modules: List[Event]):
        self.name = name
        self.ops = ops
        self.modules = modules
        self.busy = union((s, e) for s, e, _ in ops)

    def busy_ns(self) -> float:
        return length(self.busy)

    def runs_of(self, patterns: Sequence[str]) -> List[Event]:
        rx = [re.compile(p) for p in patterns]
        return [m for m in self.modules if any(r.search(m[2]) for r in rx)]


def read_xspace(path) -> Tuple[List[DeviceTimeline], List[Event]]:
    """Device timelines and the host's ``bench.*`` spans of one trace file."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
            devices.append(DeviceTimeline(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (ev.start_ns, ev.end_ns, ev.name)
                    for ev in line.events
                    if ev.name.startswith("bench.")
                )
    return devices, host


class TraceView:
    """What a per-layer metric's reader may ask of a traced run."""

    def __init__(
        self,
        devices: List[DeviceTimeline],
        host: List[Event],
        window_s: float,
        required: Dict[str, Dict[str, float]],
        peaks: Dict[str, float],
        patterns,
    ):
        self.devices = devices
        self.host = host
        self.window_s = window_s
        self.required = required
        self.peak_flops = float(peaks["bf16_flops"])
        self.peak_bytes = float(peaks["hbm_bytes_per_s"])
        self._patterns = patterns

    # ---- whole-device numbers --------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(d.busy_ns() for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    # ---- programs ------------------------------------------------------------
    def runs(self, role: str) -> List[List[Event]]:
        pats = self._patterns(role)
        return [d.runs_of(pats) for d in self.devices]

    def has_program(self, role: str) -> bool:
        return any(self.runs(role))

    def program_ms(self, role: str) -> Optional[float]:
        """Device time per run of a program, on the busiest chip."""
        per_dev = [r for r in self.runs(role) if r]
        if not per_dev:
            return None
        return max(sum(e - s for s, e, _ in r) / len(r) for r in per_dev) / 1e6

    # ---- the breakdown the ledger keeps -----------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device operations that took most time of their own (summed
        over chips, averaged per chip) and the longest idle gaps on the first
        chip, each named by the host span that covers its middle."""
        by_op: Dict[str, float] = {}
        for d in self.devices:
            for name, t in self_times(d.ops):
                name = op_name(name)
                by_op[name] = by_op.get(name, 0.0) + t
        n = max(len(self.devices), 1)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        out_ops = [[name, t / n / 1e9] for name, t in ops]
        out_gaps: List[List] = []
        if self.devices and self.devices[0].busy:
            d = self.devices[0]
            lo, hi = d.busy[0][0], d.busy[-1][1]
            idle = sorted(gaps(d.busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
            for s, e in idle:
                out_gaps.append([self.host_span_at((s + e) / 2), (e - s) / 1e9])
        return {"device_ops": out_ops, "idle_gaps": out_gaps}

    def host_span_at(self, t: float) -> str:
        """The innermost benchmark span on the host at time ``t``."""
        best = None
        for s, e, name in self.host:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "host: outside the benchmark's spans"
