"""Names the program puts on the JAX profiler's timeline, and the
stage-span recorder of the segmented executor -- the repo's APEX
analogue.

The paper's breakdown (communication vs local FFT compute, per
parcelport) is a *timeline* result. Here the timeline is the JAX
profiler's trace (``jax.profiler.start_trace``): host spans and device
operations share its clock, and the program names its own work in it.

Device scopes (``jax.named_scope``; they land in every compiled
instruction's ``op_name`` metadata when the program is traced and cost
nothing at run time):

- one stage scope per schedule stage, ``repro.stage<i>.<Kind>``
  (:func:`stage`, ``<i>`` the stage's index in ``Schedule.stages``);
- inside it one layer scope where the work happens (:func:`layer`):
  :data:`LOCAL_FFT`, :data:`EXCHANGE` (the collective call alone),
  :data:`RELAYOUT` (local transposes, packs and unpacks) and
  :data:`TWIDDLE`. Where layer scopes nest, the innermost names the op;
- the layer scopes of an app's own jitted steps between transforms:
  :data:`EVOLVE` and :data:`CHECKSUM` (NPB FT, ``repro.apps.npb_ft``).

Host spans (:func:`span`, over ``jax.profiler.TraceAnnotation``):
:data:`EXECUTE` around ``Plan.execute`` / ``Plan.inverse``, carrying the
plan's call number, and every :meth:`TraceRecorder.span`. With the
profiler off a span costs one check.

:class:`TraceRecorder` also keeps its own list of :class:`Span` records
(name + wall-clock start/duration + free-form ``args``) for the
segmented executor (``run_schedule(..., trace=rec)``: one span per
stage, Exchange spans carry backend/role/wire bytes) and ``Plan.profile``,
and exports them as Chrome-trace JSON; ``benchmarks/run.py --trace``
folds per-subprocess traces into one artifact
(:meth:`TraceRecorder.adopt`). Its consumers: ``CommParams.refine_online``
(alpha/beta re-fit from observed exchange spans),
``planner.record_observed`` and ``StepMonitor``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Any, Callable, ContextManager, Dict, Iterable, Iterator, List, Optional

import jax

#: the prefix of every name the program puts on the profiler's timeline
PREFIX = "repro."
#: host span around ``Plan.execute`` / ``Plan.inverse``
EXECUTE = PREFIX + "execute"
#: layer scopes of the device ops
LOCAL_FFT = PREFIX + "local_fft"
EXCHANGE = PREFIX + "exchange"
RELAYOUT = PREFIX + "relayout"
TWIDDLE = PREFIX + "twiddle"
#: layer scopes of NPB FT's own steps (``repro.apps.npb_ft``)
EVOLVE = PREFIX + "evolve"
CHECKSUM = PREFIX + "checksum"
LAYERS = (LOCAL_FFT, EXCHANGE, RELAYOUT, TWIDDLE, EVOLVE, CHECKSUM)

_OFF = contextlib.nullcontext()


def span(name: str, **args) -> ContextManager:
    """A host span ``name`` on the profiler's timeline, with ``args`` as
    its metadata; with the profiler off, one check and a shared no-op
    context."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)


def layer(name: str) -> ContextManager:
    """The layer scope ``name`` (one of :data:`LAYERS`) over the device
    ops traced inside it."""
    return jax.named_scope(name)


def stage(index: int, st) -> ContextManager:
    """The stage scope ``repro.stage<index>.<Kind>`` of schedule stage
    ``st``."""
    return jax.named_scope(f"{PREFIX}stage{index}.{type(st).__name__}")


@dataclasses.dataclass
class Span:
    """One completed wall-clock interval. ``t0``/``dur`` are seconds
    (``t0`` relative to the recorder's epoch); ``cat`` groups spans for
    filtering (``"exchange"`` marks collective stages); ``args`` is the
    free-form attribute payload shown in the trace viewer."""

    name: str
    t0: float
    dur: float
    cat: str = "stage"
    pid: int = 0
    tid: int = 0
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_chrome(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.t0 * 1e6,
            "dur": self.dur * 1e6,
            "pid": self.pid,
            "tid": self.tid,
            "cat": self.cat,
            "args": dict(self.args),
        }


class TraceRecorder:
    """Collects spans; exports Chrome-trace JSON.

    The clock is injectable (tests pass a fake); production uses
    ``time.perf_counter``. Recording is append-only and cheap (one
    dataclass per span) so it can stay on in serving paths. Each span
    is also entered as the host span ``repro.<name>`` on the profiler's
    timeline (:func:`span`).
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None, *, pid: int = 0):
        self._clock = clock or time.perf_counter
        self._epoch = self._clock()
        self.pid = pid
        self.spans: List[Span] = []
        self._process_names: Dict[int, str] = {}
        self._adopted: List[Dict[str, Any]] = []

    # -- recording ---------------------------------------------------------
    def now(self) -> float:
        """Seconds since the recorder was created."""
        return self._clock() - self._epoch

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "stage", tid: int = 0, **args) -> Iterator[Span]:
        """Context manager stamping one span around the enclosed work.
        Extra keyword arguments become the span's ``args``; the yielded
        span may be annotated further before the block exits."""
        sp = Span(name=name, t0=self.now(), dur=0.0, cat=cat, pid=self.pid, tid=tid, args=args)
        try:
            with span(PREFIX + name, cat=cat, **args):
                yield sp
        finally:
            sp.dur = self.now() - sp.t0
            self.spans.append(sp)

    def add_span(
        self,
        name: str,
        t0: float,
        dur: float,
        *,
        cat: str = "stage",
        tid: int = 0,
        args: Optional[Dict[str, Any]] = None,
    ) -> Span:
        """Record an already-timed interval (``t0`` in recorder-relative
        seconds, e.g. from :meth:`now`)."""
        sp = Span(name=name, t0=t0, dur=dur, cat=cat, pid=self.pid, tid=tid, args=dict(args or {}))
        self.spans.append(sp)
        return sp

    # -- queries -----------------------------------------------------------
    def mark(self) -> int:
        """Bookmark for :meth:`spans_since` (e.g. per serve dispatch)."""
        return len(self.spans)

    def spans_since(self, mark: int) -> List[Span]:
        return self.spans[mark:]

    def exchange_spans(self) -> List[Span]:
        """The collective-stage spans (``cat == "exchange"``) -- what
        ``CommParams.refine_online`` fits against."""
        return [s for s in self.spans if s.cat == "exchange"]

    # -- adoption ----------------------------------------------------------
    def set_process_name(self, pid: int, name: str) -> None:
        self._process_names[pid] = name

    def adopt(
        self,
        events: Iterable[Dict[str, Any]],
        *,
        pid: Optional[int] = None,
        name: Optional[str] = None,
    ) -> None:
        """Fold pre-exported Chrome events (e.g. printed by a benchmark
        subprocess) into this recorder under their own pid row. Events
        keep their source-relative timestamps -- different processes do
        not share a clock, so rows line up per-pid, not globally."""
        events = list(events)
        if pid is None:
            used = {e.get("pid", 0) for e in self._adopted} | {s.pid for s in self.spans}
            used.add(self.pid)
            pid = max(used) + 1
        for e in events:
            e = dict(e)
            e["pid"] = pid
            self._adopted.append(e)
        if name is not None:
            self.set_process_name(pid, name)

    # -- exports -----------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """The ``chrome://tracing`` / Perfetto JSON object."""
        events = [s.to_chrome() for s in self.spans] + list(self._adopted)
        for pid, pname in sorted(self._process_names.items()):
            events.append({
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": pname},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)
