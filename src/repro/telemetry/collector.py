"""StageFrontier monitor: the always-on integration used by the train loop.

Wires together the rank-local StageRecorder, the sampled device-time side
channel, the failure-safe window gather, the streaming WindowAggregator +
deterministic labeler, evidence packets, and the operational policy —
the full paper pipeline behind two calls:

    mon = Monitor(schema, rank=..., transport=...)
    with mon.step():
        with mon.stage("data.next_wait"): batch = next(it)
        ...
    report = mon.end_of_step(outputs)   # gathers/labels at window boundaries

The Monitor counts its own host cost in `Monitor.metrics`, a
`repro.obs.MetricsRegistry` (integer nanoseconds, so two ranks'
registries merge exactly): `monitor.record_ns` is what it spends on every
step (the recorder's bookkeeping, `observe_output`, the per-step fold of
`end_of_step`), `monitor.window_close_ns` what the window close (gather,
label, emit) costs.  Each stage is also a profiler annotation
`monitor.<stage>`, and the window close `monitor.window_close`.
"""
from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np
from jax.profiler import TraceAnnotation

from ..core.contract import StageSchema
from ..core.labeler import LabelerGates
from ..core.windows import WindowAggregator, WindowReport
from ..distributed.policy import Action, MonitorPolicy
from ..obs.metrics import MetricsRegistry
from .device_events import DeviceEventChannel
from .gather import GatherResult, TelemetryGather
from .packets import EvidencePacket, from_diagnosis
from .recorder import StageRecorder

__all__ = ["Monitor"]

_ns = time.perf_counter_ns


def _mirror(counter, total: int) -> None:
    """Bring a counter up to a running total kept elsewhere."""
    if total != counter.value:
        counter.inc(total - counter.value)


class Monitor:
    """Per-process StageFrontier runtime (rank 0 also labels and routes)."""

    def __init__(
        self,
        schema: StageSchema,
        *,
        rank: int = 0,
        transport=None,
        window_steps: int = 100,
        event_q: float = 0.05,
        gates: LabelerGates | None = None,
        policy: MonitorPolicy | None = None,
        on_action: Callable[[Action], None] | None = None,
        keep_windows: bool = False,
    ):
        self.schema = schema
        self.rank = rank
        self.recorder = StageRecorder(schema, span_prefix="monitor.")
        self.events = DeviceEventChannel(event_q)
        self.gatherer = (
            TelemetryGather(transport, rank) if transport is not None else None
        )
        self.aggregator = WindowAggregator(schema, window_steps=window_steps, gates=gates)
        self.policy = policy or MonitorPolicy()
        self.on_action = on_action
        self.window_steps = window_steps
        self.packets: list[EvidencePacket] = []
        self.actions: list[Action] = []
        self.keep_windows = keep_windows
        self._local_rows: list[np.ndarray] = []
        self._local_walls: list[float] = []
        #: the Monitor's own counts (`docs/observability.md`)
        self.metrics = MetricsRegistry()
        m = self.metrics
        self._record_ns = m.counter("monitor.record_ns")
        self._close_ns = m.counter("monitor.window_close_ns")
        self._steps = m.counter("monitor.steps")
        self._windows = m.counter("monitor.windows")
        self._dropped = m.counter("monitor.dropped_spans")
        self._event_attempts = m.counter("monitor.event_attempts")
        self._event_dropped = m.counter("monitor.event_dropped")
        self._event_seconds = m.histogram("monitor.event_device_seconds")
        #: the recorder's `own_ns` already in `monitor.record_ns`
        self._recorder_counted = 0
        #: `observe_output` time not yet in `monitor.record_ns`
        self._observe_ns = 0

    # -- recording ---------------------------------------------------------------

    def step(self):
        return self.recorder.step()

    def stage(self, name: str):
        return self.recorder.stage(name)

    def observe_output(self, output: Any, cpu_wall_ms: float) -> None:
        """Sampled device-time channel; call right after step dispatch."""
        t = _ns()
        self.events.observe(self.recorder._step_index, output, cpu_wall_ms)
        self._observe_ns += _ns() - t

    # -- window boundary ------------------------------------------------------------

    def end_of_step(self) -> WindowReport | None:
        """Fold the last recorded step; gathers + labels at window closes."""
        t_fold = _ns()
        rec = self.recorder
        last = rec.last()
        if last is None:
            return None
        self._local_rows.append(np.array(last.vector(self.schema)))
        self._local_walls.append(last.wall)
        for step, device_ms, cpu_ms in self.events.poll():
            self.aggregator.add_event_sample(device_ms, cpu_ms)
            self._event_seconds.observe(device_ms * 1e-3)
        self._steps.inc()
        _mirror(self._dropped, rec.dropped_spans)
        _mirror(self._event_attempts, self.events.attempts)
        _mirror(self._event_dropped, self.events.dropped)
        own = rec.own_ns
        self._record_ns.inc(
            own - self._recorder_counted + self._observe_ns + (_ns() - t_fold)
        )
        self._recorder_counted, self._observe_ns = own, 0
        if len(self._local_rows) < self.window_steps:
            return None
        t0 = _ns()
        with TraceAnnotation("monitor.window_close"):
            report = self._close_window()
        self._close_ns.inc(_ns() - t0)
        return report

    def _close_window(self) -> WindowReport | None:
        """Gather the window's rows, label them, emit the packet and the
        policy's actions."""
        self._windows.inc()
        local = np.stack(self._local_rows)           # [N, S]
        walls = np.array(self._local_walls)
        self._local_rows.clear()
        self._local_walls.clear()

        gather_ok = True
        present = None
        if self.gatherer is not None:
            result: GatherResult = self.gatherer.gather_window(local)
            gather_ok = result.ok
            present = result.present_ranks
            if result.ok:
                window = result.window
            else:
                # degraded: zero-fill missing ranks; present_ranks tells the
                # labeler to cap confidence (telemetry_limited), local rows
                # still support safe local summaries.
                r = self.schema.world_size
                window = np.zeros((local.shape[0], r, local.shape[1]))
                for rr, part in enumerate(result.parts or ()):
                    if part is not None and rr < r:
                        window[:, rr, :] = part
                if self.rank < r:
                    window[:, self.rank, :] = local
        else:
            window = local[:, None, :]               # single-process view

        report = None
        for i in range(window.shape[0]):
            report = self.aggregator.add_step(
                window[i],
                walls[i] if window.shape[1] == 1 else window[i].sum(-1),
                gather_ok=gather_ok,
                present_ranks=present,
            ) or report
        report = report or self.aggregator.flush()
        if report is not None:
            pkt = from_diagnosis(
                report.diagnosis,
                self.schema.stages,
                report.steps,
                window.shape[1],
                report.window_index,
                window=report.durations if self.keep_windows else None,
                present_ranks=tuple(present) if present is not None else (),
            )
            self.packets.append(pkt)
            acts = self.policy.on_report(report)
            self.actions.extend(acts)
            if self.on_action is not None:
                for a in acts:
                    try:
                        self.on_action(a)
                    except Exception:
                        pass  # monitoring never fails training
        return report

    # -- summaries --------------------------------------------------------------------

    @property
    def monitor_path_seconds(self) -> float:
        """Cumulative seconds spent on gather+label (the overhead
        numerator): `monitor.window_close_ns` in seconds."""
        return self._close_ns.value / 1e9

    def overhead_fraction(self, train_seconds: float) -> float:
        """Gather-path time / training time (the paper's rho)."""
        return self.monitor_path_seconds / max(train_seconds, 1e-9)

    def total_overhead_fraction(self, train_seconds: float) -> float:
        """All of the Monitor's host time (`monitor.record_ns` plus
        `monitor.window_close_ns`) / training time."""
        total = self._record_ns.value + self._close_ns.value
        return total / 1e9 / max(train_seconds, 1e-9)
