"""Reduce a JAX profiler trace (``.xplane.pb``) to per-layer device times.

The program names no scopes of its own, so each device operation is put
in a layer by what it is:

* **exchange**: collectives and their asynchronous start/done halves
  (all-to-all, all-gather, all-reduce, reduce-scatter,
  collective-permute, send/recv), by opcode;
* **local_fft**: what the compiled module's metadata places under
  XLA's FFT (``jit(fft)``: on the TPU a decomposition into 128-point DFT
  matmuls, twiddle fusions, complex plane splits and copies), the dots
  of the matmul DFT (``dot_general``), and the Pallas FFT kernels
  (``pallas_call``, ``tpu_custom_call``);
* **relayout**: everything else: the schedule's transposes, copies and
  slices, and the split of the complex input into planes that XLA
  attributes to the input and not to the FFT.

The trace names each operation by its HLO text but carries no metadata,
so the JAX op path of an instruction is looked up in the compiled HLO
text of its module, which the driver hands over. Where it does not
(another module), only the instruction's name and opcode count:
convolutions and ``fft`` are local FFT, collectives the exchange, the
rest relayout. Limits: a fusion that XLA forms across two layers counts
whole in the layer of its root; two modules of one name share one table.

Times are read from the ``XLA Ops`` line of each ``/device:`` plane,
clipped to the host span ``bench.window`` that the harness puts around
the traced part of the window. Device and host events share the
profiler's clock.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
#: entries of each breakdown list
TOP = 10

_COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|send|recv)(-start|-done)?$"
)
_LOCAL_FFT_PATH = re.compile(r"jit\(fft\)|/fft(/|$)|dot_general|pallas_call")
_EVENT = re.compile(r"^%?([\w.-]+) = ")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_SUFFIX = re.compile(r"\.\d+$")
_MODULE = re.compile(r"^HloModule ([\w.-]+)", re.M)
_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = .*?op_name="([^"]*)"', re.M)


def parse_event(text: str) -> tuple:
    """(instruction, opcode, custom-call target) of an ``XLA Ops`` event,
    whose name is the instruction's HLO text."""
    m = _EVENT.match(text)
    if not m:
        return text, "", ""
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    target = _TARGET.search(rest)
    return m.group(1), op.group(1) if op else "", target.group(1) if target else ""


def op_class(instr: str, opcode: str, op_path: str = "", target: str = "") -> str:
    """The layer of one device operation; ``op_path`` is its JAX op path
    from the module's metadata, where known."""
    if _COLLECTIVE.match(opcode):
        return "exchange"
    if (_LOCAL_FFT_PATH.search(op_path) or opcode in ("fft", "convolution")
            or "convolution" in instr or target == "tpu_custom_call"):
        return "local_fft"
    return "relayout"


def hlo_op_paths(texts) -> dict:
    """module name -> {instruction -> JAX op path}, from compiled HLO text."""
    out = {}
    for text in texts:
        m = _MODULE.search(text)
        if m:
            out.setdefault(m.group(1), {}).update(_INSTR.findall(text))
    return out


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(s: float, e: float, merged) -> float:
    """Length of ``[s, e)`` covered by merged intervals."""
    total = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        total += min(b, e) - max(a, s)
    return total


@dataclass
class Device:
    name: str
    window: tuple
    ops: list = field(default_factory=list)  # (start_ns, end_ns, label, class)

    def merged(self, keep=lambda c: True) -> list:
        return union((s, e) for s, e, _, c in self.ops if keep(c))

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.merged()) * 1e-9

    def class_s(self, cls: str) -> float:
        return sum(e - s for s, e, _, c in self.ops if c == cls) * 1e-9

    @property
    def exposed_exchange_s(self) -> float:
        """Collective time during which no other operation runs here."""
        others = self.merged(lambda c: c != "exchange")
        exch = self.merged(lambda c: c == "exchange")
        return sum((e - s) - overlap(s, e, others) for s, e in exch) * 1e-9

    def gaps(self) -> list:
        """Idle ``[start, end)`` stretches of the window, in ns."""
        out, t = [], self.window[0]
        for s, e in self.merged():
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def by_op(self) -> dict:
        out = {}
        for s, e, label, cls in self.ops:
            key = f"{cls}:{label}"
            out[key] = out.get(key, 0.0) + (e - s) * 1e-9
        return out


@dataclass
class Reduced:
    devices: list
    window_ns: tuple
    steps: int
    host_spans: list  # (start_ns, end_ns, name)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips (0 with no device ops)."""
        if not self.devices:
            return 0.0
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share_pct(self) -> float | None:
        if not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def per_step_max(self, seconds_of) -> float | None:
        """The slowest device's seconds per traced step, or None where
        nothing was traced or no step was."""
        if not self.devices or not self.steps:
            return None
        return max(seconds_of(d) for d in self.devices) / self.steps

    def host_phase(self, s: float, e: float) -> str:
        """The host span that covers most of ``[s, e)``."""
        best, name = 0.0, "no host span"
        for a, b, n in self.host_spans:
            cover = min(b, e) - max(a, s)
            if cover > best:
                best, name = cover, n
        return name

    def breakdown(self) -> dict:
        """The device operations that took most time (seconds, averaged
        over the chips) and the longest idle gaps of the first chip, each
        named by the host span it fell in."""
        ops = {}
        for d in self.devices:
            for k, v in d.by_op().items():
                ops[k] = ops.get(k, 0.0) + v / len(self.devices)
        device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = self.devices[0].gaps() if self.devices else []
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
        idle = [[self.host_phase(s, e), (e - s) * 1e-9] for s, e in gaps]
        return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": idle}


def _module_at(modules, t: float) -> str:
    for s, e, name in modules:
        if s <= t < e:
            return name
    return ""


def reduce_profile(profile, steps_span: str | None = None,
                   op_paths: dict | None = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; ``op_paths`` as
    :func:`hlo_op_paths` gives them for the modules the window ran."""
    op_paths = op_paths or {}
    host_events = []
    device_planes = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_events.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in host_events if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = windows[0]
    spans = [(s, e, n) for s, e, n in host_events if n != WINDOW_SPAN and s < w1 and e > w0]
    steps = sum(1 for s, e, n in spans if n == steps_span) if steps_span else 0
    devices = []
    for plane in device_planes:
        dev = Device(plane.name, (w0, w1))
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (ev.start_ns, ev.end_ns, ev.name.split("(")[0])
            for ev in (lines[MODULE_LINE].events if MODULE_LINE in lines else [])
        )
        for ev in lines[OP_LINE].events if OP_LINE in lines else []:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            instr, opcode, target = parse_event(ev.name)
            paths = op_paths.get(_module_at(modules, ev.start_ns), {})
            cls = op_class(instr, opcode, paths.get(instr, ""), target)
            label = _SUFFIX.sub("", instr) + (f"[{target}]" if target else "")
            dev.ops.append((s, e, label, cls))
        if dev.ops:
            devices.append(dev)
    return Reduced(devices, (w0, w1), steps, spans)


def reduce_xspace(path: Path, steps_span: str | None = None,
                  op_paths: dict | None = None) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), steps_span, op_paths)
