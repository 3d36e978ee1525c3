"""Reduce a JAX profiler trace to the program's own layers and stages.

The program names its work (``repro.obs.trace``): every device op of a
plan carries a stage scope ``repro.stage<i>.<Kind>`` and a layer scope
(``repro.local_fft``, ``repro.exchange``, ``repro.relayout``,
``repro.twiddle``) in its op path, and ``Plan.execute`` runs under the
host span ``repro.execute``. This module reads those names, beside
:mod:`trace_reduce`, which classifies ops by opcode and op path and is
left as it is.

Each op of a module whose compiled HLO carries ``repro.`` scopes (the
plan's) falls in one bucket:

* **exchange**: a collective, by opcode (as ``exchange_ms`` counts it);
* else the innermost layer scope of its op path; a non-collective op
  under ``repro.exchange`` is the pack or unpack XLA builds around the
  collective, and counts as **relayout**;
* **unscoped**: no ``repro.`` scope at all: what XLA adds at the
  program's boundary (splitting the complex argument into planes,
  combining the result's, parameter copies).

Per-transform figures divide by the traced steps and take the slowest
chip, as :mod:`trace_reduce` does. ``dispatch_ms`` is the self time of
the ``repro.execute`` spans in the window (less any ``repro.`` span
nested in them) over the steps. Idle gaps are named after the innermost
host span (``bench.*`` or ``repro.*``) covering most of them.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

import trace_reduce as tr

SCOPE_PREFIX = "repro."
EXECUTE_SPAN = "repro.execute"
HOST_PREFIXES = (tr.HOST_SPAN_PREFIX, SCOPE_PREFIX)
BUCKETS = ("local_fft", "exchange", "relayout", "twiddle", "unscoped")

_LAYER = re.compile(r"(?:^|/)repro\.(local_fft|exchange|relayout|twiddle)(?=/|$)")
_STAGE = re.compile(r"(?:^|/)(repro\.stage(\d+)\.\w+)(?=/|$)")


def bucket(instr: str, opcode: str, op_path: str, target: str = "") -> str:
    """The bucket of one op of the plan's module (module docstring)."""
    if tr.op_class(instr, opcode, "", target) == "exchange":
        return "exchange"
    layers = _LAYER.findall(op_path)
    if not layers:
        return "unscoped"
    return "relayout" if layers[-1] == "exchange" else layers[-1]


def stage_of(op_path: str) -> str:
    """The innermost stage scope of an op path ("" outside every stage)."""
    stages = _STAGE.findall(op_path)
    return stages[-1][0] if stages else ""


def scoped_modules(op_paths: dict) -> set:
    """The modules some of whose instructions carry a ``repro.`` scope."""
    return {m for m, table in op_paths.items()
            if any(SCOPE_PREFIX in p for p in table.values())}


def innermost_cover(s: int, e: int, spans) -> dict:
    """name -> ns of ``[s, e)`` during which that span is the innermost
    (shortest) of ``spans`` ((start, end, name)) covering the instant."""
    inside = [(a, b, n) for a, b, n in spans if a < e and b > s]
    cuts = sorted({s, e} | {t for a, b, _ in inside for t in (a, b) if s < t < e})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        covering = [(sb - sa, n) for sa, sb, n in inside if sa <= a and sb >= b]
        name = min(covering)[1] if covering else "no host span"
        out[name] = out.get(name, 0) + (b - a)
    return out


@dataclass
class Chip(tr.Device):
    """A chip's ops (``ops``, every module: its busy time and gaps) and
    the plan's ops among them."""

    plan: list = field(default_factory=list)  # (start_ns, end_ns, label, bucket, stage)

    def bucket_s(self, *names) -> float:
        return sum(e - s for s, e, _, b, _ in self.plan if b in names) * 1e-9

    def plan_busy_s(self) -> float:
        return sum(e - s for s, e in tr.union((s, e) for s, e, *_ in self.plan)) * 1e-9

    def stage_s(self, stage: str, *names) -> float:
        return sum(e - s for s, e, _, b, st in self.plan
                   if st == stage and (not names or b in names)) * 1e-9


@dataclass
class Scoped:
    chips: list
    window_ns: tuple
    steps: int
    host_spans: list  # (start_ns, end_ns, name, line)

    def per_step_ms(self, seconds_of) -> float | None:
        """The slowest chip's milliseconds per traced step, or None."""
        if not self.chips or not self.steps:
            return None
        return 1e3 * max(seconds_of(c) for c in self.chips) / self.steps

    def bucket_ms(self, *names) -> float | None:
        if not any(c.bucket_s(*names) for c in self.chips):
            return None
        return self.per_step_ms(lambda c: c.bucket_s(*names))

    def dispatch_ms(self) -> float | None:
        """Self time of ``repro.execute`` per traced step."""
        execs = [sp for sp in self.host_spans if sp[2] == EXECUTE_SPAN]
        if not execs or not self.steps:
            return None
        total = 0
        for s, e, _, line in execs:
            nested = tr.union(
                (max(a, s), min(b, e)) for a, b, n, ln in self.host_spans
                if ln == line and n.startswith(SCOPE_PREFIX) and n != EXECUTE_SPAN
                and a < e and b > s)
            total += (e - s) - sum(b - a for a, b in nested)
        return 1e-6 * total / self.steps

    def metrics(self) -> dict:
        """The per-layer metrics that read the program's scopes and span;
        a metric with nothing to read is left out."""
        out = {
            "dispatch_ms": self.dispatch_ms(),
            "local_fft_scoped_ms": self.bucket_ms("local_fft"),
            "relayout_scoped_ms": self.bucket_ms("relayout", "twiddle"),
            "unscoped_ms": self.bucket_ms("unscoped"),
        }
        return {k: v for k, v in out.items() if v is not None}

    def stage_names(self) -> list:
        names = {st for c in self.chips for *_, st in c.plan if st}
        return sorted(names, key=lambda n: int(_STAGE.search(n).group(2)))

    def stages(self) -> list:
        """[stage scope, device ms per transform on the slowest chip]."""
        return [[st, self.per_step_ms(lambda c: c.stage_s(st))] for st in self.stage_names()]

    def stage_layers(self) -> list:
        """[stage scope, bucket, ms per transform] where that is above 0."""
        out = []
        for st in self.stage_names():
            for b in BUCKETS:
                ms = self.per_step_ms(lambda c: c.stage_s(st, b))
                if ms:
                    out.append([st, b, ms])
        return out

    def consistency(self) -> dict:
        """The four buckets against the plan's busy time per transform,
        on the slowest chip (each op lies in one bucket, so they agree
        unless ops of the plan overlap in time)."""
        parts = self.per_step_ms(lambda c: c.bucket_s(*BUCKETS))
        busy = self.per_step_ms(lambda c: c.plan_busy_s())
        return {"buckets_ms": parts, "plan_busy_ms": busy,
                "rel_diff": abs(parts - busy) / busy if busy else None}

    def idle(self) -> tuple:
        """The longest idle gaps of the first chip, each named after the
        innermost host span covering most of it, and the idle seconds
        under each innermost span over the whole window."""
        if not self.chips:
            return [], {}
        spans = [(s, e, n) for s, e, n, _ in self.host_spans]
        named, by_span = [], {}
        for s, e in self.chips[0].gaps():
            cover = innermost_cover(s, e, spans)
            for n, ns in cover.items():
                by_span[n] = by_span.get(n, 0.0) + ns * 1e-9
            named.append([max(cover.items(), key=lambda kv: kv[1])[0], (e - s) * 1e-9])
        named.sort(key=lambda g: -g[1])
        return named[:tr.TOP], by_span

    def unscoped_ops(self) -> list:
        """[label, ms per transform] of the unscoped ops, averaged over chips."""
        ops = {}
        for c in self.chips:
            for s, e, label, b, _ in c.plan:
                if b == "unscoped":
                    ops[label] = ops.get(label, 0.0) + (e - s) * 1e-6 / len(self.chips)
        return sorted(([k, v / max(self.steps, 1)] for k, v in ops.items()),
                      key=lambda kv: -kv[1])[:tr.TOP]

    def summary(self) -> dict:
        gaps, by_span = self.idle()
        return {"metrics": self.metrics(), "stages": self.stages(),
                "stage_layers": self.stage_layers(), "consistency": self.consistency(),
                "unscoped_ops": self.unscoped_ops(), "idle_gaps": gaps,
                "idle_by_span": by_span, "steps": self.steps}


def reduce_profile(profile, steps_span: str | None, hlo_texts) -> Scoped:
    """Reduce a ``jax.profiler.ProfileData`` with the compiled HLO text of
    the modules the window ran (as ``programs()`` of the cell hands them over)."""
    op_paths = tr.hlo_op_paths(hlo_texts)
    plan_modules = scoped_modules(op_paths)
    host, device_planes = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.start_ns, ev.end_ns, ev.name, line.name))
    windows = [(s, e) for s, e, n, _ in host if n == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {tr.WINDOW_SPAN} span in the trace")
    w0, w1 = windows[0]
    spans = [sp for sp in host if sp[2] != tr.WINDOW_SPAN and sp[0] < w1 and sp[1] > w0]
    steps = sum(1 for sp in spans if sp[2] == steps_span) if steps_span else 0
    chips = []
    for plane in device_planes:
        chip = Chip(plane.name, (w0, w1))
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (ev.start_ns, ev.end_ns, ev.name.split("(")[0])
            for ev in (lines[tr.MODULE_LINE].events if tr.MODULE_LINE in lines else [])
        )
        for ev in lines[tr.OP_LINE].events if tr.OP_LINE in lines else []:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            instr, opcode, target = tr.parse_event(ev.name)
            label = tr._SUFFIX.sub("", instr) + (f"[{target}]" if target else "")
            chip.ops.append((s, e, label, ""))
            module = tr._module_at(modules, ev.start_ns)
            if module in plan_modules:
                path = op_paths[module].get(instr, "")
                chip.plan.append((s, e, label, bucket(instr, opcode, path, target),
                                  stage_of(path)))
        if chip.ops:
            chips.append(chip)
    return Scoped(chips, (w0, w1), steps, spans)


def reduce_xspace(path: Path, steps_span: str | None, hlo_texts) -> Scoped:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), steps_span, hlo_texts)

