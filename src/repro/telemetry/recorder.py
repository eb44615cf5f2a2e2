"""Always-on stage recorder (paper §5): `perf.step()` / `perf.stage()`.

CPU wall-clock (`time.perf_counter_ns`) stage spans with:
  - ordered-stage non-overlap enforcement (nested ordered spans rejected;
    nested measurements allowed only as side channels),
  - residual closure (step wall minus explicit spans -> step.other),
  - prefetch-aware data alignment: a `data.next_wait` recorded before the
    first compute span of step t is charged to step t (the consuming step),
  - bounded history (always-on means bounded queues),
  - zero hot-path device synchronization,
  - one profiler annotation (`jax.profiler.TraceAnnotation`, named
    `span_prefix + stage`) over each stage it times, so a device trace
    lines the stages up with the device's work on the profiler's clock;
    dropped and re-entrant stages get none,
  - its own cost: `own_ns` sums, in integer nanoseconds, the time spent
    in its bookkeeping (step open/close, each stage's entry and exit
    outside the timed body).

The recorder is rank-local; the window aggregation and gather live in
repro.telemetry.collector / repro.core.windows.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Iterator

from jax.profiler import TraceAnnotation

from ..core.contract import StageSchema

__all__ = ["StageRecorder", "StepRecord"]

_ns = time.perf_counter_ns

# what a `stage()` block does with its body (see `_Stage.__enter__`)
_DROPPED, _ORDERED, _PREFETCH = 0, 1, 2


@dataclasses.dataclass
class StepRecord:
    """One step's ordered stage durations + metadata."""

    step: int
    durations: dict[str, float]           # ordered stage name -> seconds
    wall: float                           # step wall time (seconds)
    side: dict[str, float] = dataclasses.field(default_factory=dict)

    def vector(self, schema: StageSchema) -> list[float]:
        return [self.durations.get(s, 0.0) for s in schema.stages]


class _Stage:
    """One `stage()` block.  A class rather than a generator, so that the
    recorder's own time on both sides of the body (from the `stage()`
    call to the body's first instant, and from its last to the block's
    exit) is counted into `own_ns`."""

    __slots__ = ("_rec", "_name", "_called", "_t0", "_mode", "_span")

    def __init__(self, rec: "StageRecorder", name: str, called: int):
        self._rec, self._name, self._called = rec, name, called

    def __enter__(self) -> None:
        rec, name = self._rec, self._name
        if rec._active_stage is not None or not rec._in_step:
            if name == "data.next_wait" and not rec._in_step:
                # prefetch path: charged to the consuming step
                mode = _PREFETCH
            else:
                rec.dropped_spans += 1
                mode = _DROPPED
        elif name not in rec._span_names:
            rec.dropped_spans += 1
            mode = _DROPPED
        else:
            rec._active_stage = name
            mode = _ORDERED
        self._mode = mode
        if mode != _DROPPED:
            self._span = TraceAnnotation(rec.span_name(name))
            self._span.__enter__()
        self._t0 = t0 = _ns()
        rec.own_ns += t0 - self._called

    def __exit__(self, *exc) -> bool:
        t1 = _ns()
        rec, mode = self._rec, self._mode
        if mode != _DROPPED:
            self._span.__exit__(None, None, None)
            seconds = (t1 - self._t0) * 1e-9
            if mode == _ORDERED:
                rec._cur[self._name] = rec._cur.get(self._name, 0.0) + seconds
                rec._active_stage = None
            else:
                rec._pending_data_wait += seconds
        rec.own_ns += _ns() - t1
        return False


class _Step:
    """One `step()` block: `begin_step` on entry, `end_step` on exit."""

    __slots__ = ("_rec", "_opened")

    def __init__(self, rec: "StageRecorder"):
        self._rec = rec

    def __enter__(self) -> "StageRecorder":
        self._opened = self._rec.begin_step()
        return self._rec

    def __exit__(self, *exc) -> bool:
        if self._opened:
            self._rec.end_step()
        return False


class StageRecorder:
    """Rank-local ordered-stage timing with contract enforcement.

    `span_prefix` names the profiler annotation of each stage
    (`span_prefix + stage`): the Monitor's recorder uses ``"monitor."``,
    the fleet's tick line none (its phases are already ``tick.*``)."""

    def __init__(
        self,
        schema: StageSchema,
        *,
        max_history: int = 4096,
        span_prefix: str = "",
    ):
        self.schema = schema
        self.span_prefix = span_prefix
        self._span_names = {s: span_prefix + s for s in schema.stages}
        self._history: deque[StepRecord] = deque(maxlen=max_history)
        self._step_index = 0
        self._in_step = False
        self._active_stage: str | None = None
        self._cur: dict[str, float] = {}
        self._side: dict[str, float] = {}
        self._step_start = 0
        #: a data wait measured outside a step is charged to the NEXT step
        #: (the consuming one) — prefetch-aware alignment.
        self._pending_data_wait = 0.0
        self.dropped_spans = 0
        #: integer nanoseconds spent in the recorder's own bookkeeping
        #: (never in a stage's body): what always-on recording costs.
        self.own_ns = 0

    def span_name(self, stage: str) -> str:
        """The profiler annotation's name for `stage`."""
        return self._span_names.get(stage) or self.span_prefix + stage

    # -- step context -----------------------------------------------------------

    @property
    def in_step(self) -> bool:
        """True between `begin_step()` and `end_step()` (public span API:
        service-side instrumentation checks this before opening a step
        lazily — see `repro.obs.ObsTickline`)."""
        return self._in_step

    @property
    def active_stage(self) -> str | None:
        """Name of the currently open ordered span, or None.  Lets a
        caller detect re-entrancy (a nested service call inside an
        instrumented phase) and skip instead of violating non-overlap."""
        return self._active_stage

    def begin_step(self) -> bool:
        """Open a step span manually; returns False (and counts the
        dropped span) if one is already open.  The manual lifecycle is
        the span API `repro.obs` needs: a service tick's phases span
        several method calls, so the step cannot be a single `with`."""
        t = _ns()
        if self._in_step:  # nested steps are a contract violation: drop inner
            self.dropped_spans += 1
            self.own_ns += _ns() - t
            return False
        self._in_step = True
        self._cur = {}
        self._side = {}
        self._step_start = t
        if self._pending_data_wait:
            self._cur["data.next_wait"] = self._pending_data_wait
            self._pending_data_wait = 0.0
        self.own_ns += _ns() - t
        return True

    def end_step(self) -> StepRecord | None:
        """Close the open step span: residual closure, history append.
        Returns the finished record (None if no step was open)."""
        if not self._in_step:
            return None
        t = _ns()
        wall = (t - self._step_start) * 1e-9
        explicit = sum(
            v for k, v in self._cur.items()
            if k in self.schema.stages and not k.endswith("other_cpu_wall")
        )
        residual = self.schema.residual_index
        if residual is not None:
            self._cur[self.schema.stages[residual]] = max(0.0, wall - explicit)
        record = StepRecord(
            step=self._step_index,
            durations=dict(self._cur),
            wall=wall,
            side=dict(self._side),
        )
        self._history.append(record)
        self._step_index += 1
        self._in_step = False
        self._active_stage = None
        self.own_ns += _ns() - t
        return record

    def step(self) -> _Step:
        return _Step(self)

    # -- stage contexts ------------------------------------------------------------

    def stage(self, name: str) -> _Stage:
        """Ordered frontier stage. Nested ordered spans are rejected
        (recorded as dropped, never raised into training)."""
        return _Stage(self, name, _ns())

    @contextlib.contextmanager
    def side_channel(self, name: str) -> Iterator[None]:
        """Nested measurement allowed anywhere; never enters the prefix
        vector (side_channel=true in the contract)."""
        t0 = _ns()
        try:
            yield
        finally:
            self._side[name] = self._side.get(name, 0.0) + (_ns() - t0) * 1e-9

    def add_side_value(self, name: str, value: float) -> None:
        self._side[name] = float(value)

    # -- history ---------------------------------------------------------------------

    @property
    def history(self) -> tuple[StepRecord, ...]:
        return tuple(self._history)

    def last(self) -> StepRecord | None:
        return self._history[-1] if self._history else None

    def drain(self) -> list[StepRecord]:
        out = list(self._history)
        self._history.clear()
        return out
