"""Device time by the program's own names: its scopes and its host spans.

The program names its work.  Each layer of the model runs under a
``jax.named_scope`` (``embed``, ``layers``, ``norm``, ``attn``, ``mlp``,
``kv_cache``, ``head``, ``loss``, ``optimizer``, ``grad_sync`` ...), which
XLA keeps in every compiled op's ``op_name`` metadata, and
``ServeEngine.generate`` wraps its phases in host spans (``serve.setup``,
``serve.prefill``, ``serve.decode``, ``serve.sample``, ``serve.collect``).
This module reads both from the same XSpace that ``trace.read_xspace``
reads, and adds to ``trace.TraceView`` what needs them:

* ``scoped_ms(role, scope, exclusive)``: own device time per run of a
  program of the ops under a scope;
* ``idle_ms_in(spans, per)``: device-idle time inside host spans;
* a breakdown whose ops carry their scope and whose idle gaps are named by
  the innermost span, the benchmark's or the program's.

The TPU trace names an op only by its HLO instruction, without the
metadata, so an op's ``op_name`` is looked up by instruction name in the
text of the compiled module whose run holds it (a fusion carries the
metadata of its root instruction).  An op without one (an async copy or
slice start, a loop counter the compiler added) takes the scope of the op
event that encloses it on the device timeline: the ``while`` of a layer
scan holds the ops of its body.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as tr

# the scopes the program opens, innermost last in a path
PROGRAM_SCOPES = frozenset(
    (
        "embed", "layers", "norm", "attn", "mlp", "moe", "mix", "kv_cache",
        "head", "loss", "optimizer",
        "grad_sync", "reduce_scatter", "pod_allreduce", "all_gather",
    )
)
# host spans the benchmark (bench.*) or the program (serve.*) opens
HOST_SPAN = re.compile(r"^(bench|serve)\.")
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")
_OP_NAME_IN_TEXT = re.compile(r'op_name="([^"]*)"')

Path_ = Tuple[str, ...]


def scope_path(op_name: str) -> Path_:
    """The program's scopes in an ``op_name``, outermost first:
    ``jit(step)/transpose(jvp(layers))/while/body/closed_call/attn/dot``
    gives ``("layers", "attn")``.  The last part names the primitive (which
    may be ``all_gather``), not a scope."""
    out = []
    for part in op_name.split("/")[:-1]:
        while True:
            m = _WRAPPED.fullmatch(part)
            if not m:
                break
            part = m.group(1)
        if part in PROGRAM_SCOPES:
            out.append(part)
    return tuple(out)


def inherit(ops: Sequence[tr.Event], paths: List[Optional[Path_]]) -> List[Path_]:
    """An op without a scope of its own takes that of the op event that
    encloses it (the events of one line nest)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out: List[Path_] = [()] * len(ops)
    open_: List[Tuple[float, Path_]] = []
    for i in order:
        s, e, _ = ops[i]
        while open_ and open_[-1][0] <= s:
            open_.pop()
        own = paths[i]
        path = own if own else (open_[-1][1] if open_ else ())
        out[i] = path
        open_.append((e, path))
    return out


class ScopedTimeline(tr.DeviceTimeline):
    """One chip's ops, each with its scope path (``scopes[i]`` is that of
    ``ops[i]``)."""

    def __init__(self, name: str, ops: List[tr.Event], modules: List[tr.Event], scopes: List[Path_]):
        super().__init__(name, ops, modules)
        self.scopes = scopes

    def own_times(self) -> List[Tuple[int, float]]:
        """(index into ``ops``, own time) of every op, computed once."""
        if not hasattr(self, "_own"):
            self._own = tr.self_times((s, e, i) for i, (s, e, _) in enumerate(self.ops))
        return self._own


def read_xspace(path, module_texts: Dict[str, str]):
    """Scoped device timelines and the host's benchmark and program spans
    of one trace file.  ``module_texts`` maps a module name
    (``jit_decode_step``) to its compiled HLO text."""
    import jax

    by_module = {m: op_names_in(t) for m, t in module_texts.items()}
    data = jax.profiler.ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
                elif line.name == tr.MODULES_LINE:
                    modules = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in line.events)
            starts = [m[0] for m in modules]
            names = [look_up(by_module, modules, starts, op) for op in ops]
            paths = [scope_path(n) if n else None for n in names]
            devices.append(ScopedTimeline(plane.name, ops, modules, inherit(ops, paths)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (ev.start_ns, ev.end_ns, ev.name)
                    for ev in line.events
                    if HOST_SPAN.match(ev.name)
                )
    return devices, host


def look_up(by_module, modules: List[tr.Event], starts: List[float], op: tr.Event) -> Optional[str]:
    """The ``op_name`` of ``op`` in the text of the module run that holds it
    (``modules`` sorted, ``starts`` their starts)."""
    i = bisect.bisect_right(starts, op[0]) - 1
    if i < 0 or op[0] > modules[i][1]:
        return None
    return by_module.get(modules[i][2].split("(")[0], {}).get(tr.op_name(op[2]))


def op_names_in(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of one compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", line)
        if m:
            n = _OP_NAME_IN_TEXT.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def within(intervals: Sequence[tr.Interval], t: float) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``intervals``."""
    lo, hi = 0, len(intervals)
    while lo < hi:
        mid = (lo + hi) // 2
        if intervals[mid][1] < t:
            lo = mid + 1
        else:
            hi = mid
    return lo < len(intervals) and intervals[lo][0] <= t


class ScopedView(tr.TraceView):
    """A ``TraceView`` over ``ScopedTimeline``s and the program's spans."""

    # ---- device time by scope ---------------------------------------------
    def _role_ops(self, role: str) -> List[Tuple[int, List[Tuple[Path_, float, int]]]]:
        """Per chip that ran ``role``'s program: its number of runs, and
        (scope path, own time, op index) of each op inside those runs."""
        cache = self.__dict__.setdefault("_role_cache", {})
        if role not in cache:
            out = []
            for d, runs in zip(self.devices, self.runs(role)):
                if runs:
                    spans = tr.union((s, e) for s, e, _ in runs)
                    ops = [(d.scopes[i], t, i) for i, t in d.own_times() if within(spans, d.ops[i][0])]
                    out.append((len(runs), ops))
            cache[role] = out
        return cache[role]

    def _per_run_ms(self, role: str, keep) -> Optional[float]:
        """Own time per run of ``role``'s program of the ops that ``keep``
        takes by their scope path, on the busiest chip."""
        per_dev = [sum(t for p, t, _ in ops if keep(p)) / n / 1e6 for n, ops in self._role_ops(role)]
        return max(per_dev) if per_dev else None

    def has_scopes(self, role: str) -> bool:
        """Whether any op of ``role``'s runs carries a program scope."""
        return any(p for _, ops in self._role_ops(role) for p, _, _ in ops)

    def scoped_ms(self, role: str, scope: str, exclusive: bool = False) -> Optional[float]:
        """Own device time per run of a program, on the busiest chip, of the
        ops whose scope path holds ``scope``; with ``exclusive``, of those
        whose innermost scope is ``scope``.  None where the program did not
        run or carries no scopes (a program that predates them)."""
        if not self.has_scopes(role):
            return None
        if exclusive:
            return self._per_run_ms(role, lambda p: bool(p) and p[-1] == scope)
        return self._per_run_ms(role, lambda p: scope in p)

    def by_scope(self, role: str) -> Dict[str, float]:
        """Own device time per run (ms, busiest chip) by innermost scope;
        ops with none under ``"-"``."""
        found = {p[-1] if p else "-" for _, ops in self._role_ops(role) for p, _, _ in ops}
        return {
            inner: self._per_run_ms(role, lambda p, inner=inner: (p[-1] if p else "-") == inner)
            for inner in sorted(found)
        }

    def coverage(self, role: str, top: int = 10):
        """Share of a program's own device time on ops that carry a scope,
        and the unscoped ops that take most of the rest (ms per run), on the
        first chip that ran it."""
        for n, ops in self._role_ops(role)[:1]:
            d = next(d for d, runs in zip(self.devices, self.runs(role)) if runs)
            total = sum(t for _, t, _ in ops)
            rest: Dict[str, float] = {}
            for p, t, i in ops:
                if not p:
                    name = tr.op_name(d.ops[i][2])
                    rest[name] = rest.get(name, 0.0) + t
            worst = sorted(rest.items(), key=lambda kv: -kv[1])[:top]
            share = 100.0 * (total - sum(rest.values())) / total if total else None
            return share, [[name, t / n / 1e6] for name, t in worst]
        return None, []

    # ---- idle time by host span ----------------------------------------------
    def spans_named(self, names: Iterable[str]) -> List[tr.Event]:
        names = set(names)
        return sorted(ev for ev in self.host if ev[2] in names)

    def idle_ms_in(self, spans: Sequence[str], per: str) -> Optional[float]:
        """Device-idle time inside host spans named in ``spans`` (mean over
        chips), over the number of ``per`` spans (ms)."""
        n = len(self.spans_named([per]))
        inside = tr.union((s, e) for s, e, _ in self.spans_named(spans))
        if not n or not inside or not self.devices:
            return None
        idle = sum(tr.length(tr.subtract(inside, d.busy)) for d in self.devices)
        return idle / len(self.devices) / n / 1e6

    def dispatch_leads(self, role: str, span: str) -> List[float]:
        """Start of each run of ``role`` on the first chip less the start of
        the ``span`` that dispatched it, matched in order (ns): none is
        negative where host and device share one clock."""
        runs = sorted(self.runs(role)[0]) if self.devices else []
        spans = self.spans_named([span])
        return [r[0] - s[0] for r, s in zip(runs, spans)]

    # ---- the breakdown, with scopes -----------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """As ``TraceView.breakdown``, each op named with its innermost
        scope (``copy_bitcast_fusion.5 [layers]``) and each idle gap by the
        innermost span of the benchmark or the program."""
        by_op: Dict[str, float] = {}
        for d in self.devices:
            for i, t in d.own_times():
                p = d.scopes[i]
                name = f"{tr.op_name(d.ops[i][2])} [{p[-1] if p else '-'}]"
                by_op[name] = by_op.get(name, 0.0) + t
        n = max(len(self.devices), 1)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        out = super().breakdown(top)
        out["device_ops"] = [[name, t / n / 1e9] for name, t in ops]
        return out


# ---------------------------------------------------------------------------
# per-layer readings on these names (ms)
# ---------------------------------------------------------------------------

def _sum(*parts: Optional[float]) -> Optional[float]:
    return None if parts[0] is None else sum(p or 0.0 for p in parts)


READINGS = {
    # the layer scan's own slicing and writing back of params and cache,
    # and the cache writes in attention, per decode step
    "scan_io_ms.decode": lambda v: _sum(
        v.scoped_ms("decode_step", "layers", exclusive=True), v.scoped_ms("decode_step", "kv_cache")
    ),
    "attn_ms.prefill": lambda v: v.scoped_ms("prefill", "attn"),
    "scan_io_ms.train": lambda v: v.scoped_ms("train_step", "layers", exclusive=True),
    "optimizer_ms.train": lambda v: v.scoped_ms("train_step", "optimizer"),
    # device idle while the host dispatches a token's decode and argmax,
    # per decode step; and at a batch's start and end, per batch
    "token_gap_ms.serve": lambda v: v.idle_ms_in(("serve.decode", "serve.sample"), per="serve.decode"),
    "batch_gap_ms.serve": lambda v: v.idle_ms_in(("serve.setup", "serve.collect"), per="serve.setup"),
}
