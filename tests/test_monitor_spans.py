"""The span system on the profiler's clock, and the Monitor's own counts.

What must hold:

  1. **stages are profiler annotations** — every stage the recorder
     opens (Monitor stages as ``monitor.<stage>``, tick phases under their
     own ``tick.*`` names) is one host event of a `jax.profiler` trace,
     and the window close is ``monitor.window_close``; a dropped or
     re-entrant stage leaves none.  The events' extents agree with the
     recorder's stage durations.
  2. **the Monitor counts its whole host cost** — ``monitor.record_ns``
     grows with every step and never includes the user's work inside a
     stage; ``monitor.window_close_ns`` is what `monitor_path_seconds`
     reads; the step, window, dropped-span and event-channel counters
     follow their sources; two Monitors' registries merge exactly.
  3. **``tick.kernel`` holds the device wait** — when the phase closes,
     the kernel's outputs are ready, so ``tick.epilog`` times host work.
"""
import collections
import contextlib
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import segmented_schema
from repro.core.contract import fused_schema
from repro.fleet import FleetService
from repro.obs import ObsTickline, merge_registries
from repro.telemetry import Monitor, StageRecorder
from repro.telemetry.packets import EvidencePacket


def trace_events(tmp_path, fn, prefixes=("monitor.", "tick.")) -> list:
    """Run `fn` under a profiler trace; return the host events whose name
    starts with one of `prefixes`, as ``(name, seconds)`` in start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        (ev.start_ns, ev.name, ev.duration_ns * 1e-9)
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(prefixes)
    ]
    return [(name, sec) for _, name, sec in sorted(events)]


def monitored_steps(mon: Monitor, steps: int, sleep_s: float = 0.0) -> None:
    """`launch/train.py`'s stage order, with `sleep_s` of user work in the
    data stage."""
    for _ in range(steps):
        with mon.step():
            with mon.stage("data.next_wait"):
                time.sleep(sleep_s)
            with mon.stage("step.dispatch_cpu_wall"):
                pass
            with mon.stage("step.device_wait_cpu_wall"):
                pass
        mon.end_of_step()


# -- stages as profiler annotations ---------------------------------------


class TestAnnotations:
    def test_one_event_per_opened_stage(self, tmp_path):
        mon = Monitor(fused_schema(world_size=1), window_steps=2)

        def body():
            with mon.stage("data.next_wait"):       # prefetch: timed, charged
                pass
            for _ in range(2):
                with mon.step():
                    with mon.stage("data.next_wait"):
                        with mon.stage("step.dispatch_cpu_wall"):  # nested
                            pass
                    with mon.stage("not.a.stage"):              # unknown
                        pass
                    with mon.stage("ckpt.cpu_wall"):
                        pass
                mon.end_of_step()
            with mon.stage("ckpt.cpu_wall"):        # outside a step
                pass

        names = [n for n, _ in trace_events(tmp_path, body)]
        assert names == [
            "monitor.data.next_wait",
            "monitor.data.next_wait", "monitor.ckpt.cpu_wall",
            "monitor.data.next_wait", "monitor.ckpt.cpu_wall",
            "monitor.window_close",
        ]
        assert mon.recorder.dropped_spans == 5
        assert mon.metrics.counters()["monitor.dropped_spans"] == 4  # folded

    def test_tick_phases_keep_their_names(self, tmp_path):
        tl = ObsTickline()

        def body():
            with tl.phase("tick.decode"):
                with tl.phase("tick.regimes"):      # re-entrant: absorbed
                    pass
            with tl.phase("tick.kernel"):
                pass
            tl.close_tick()
            with tl.phase("tick.route"):
                pass
            tl.close_tick()

        names = [n for n, _ in trace_events(tmp_path, body)]
        assert names == ["tick.decode", "tick.kernel", "tick.route"]
        assert tl.recorder.dropped_spans == 0

    def test_recorder_prefix_names_its_spans(self):
        rec = StageRecorder(segmented_schema(), span_prefix="rank3.")
        assert rec.span_name("data.next_wait") == "rank3.data.next_wait"
        assert StageRecorder(segmented_schema()).span_name("x") == "x"

    def test_event_extents_match_the_stage_vectors(self, tmp_path):
        mon = Monitor(fused_schema(world_size=1), window_steps=100)
        events = trace_events(
            tmp_path, lambda: monitored_steps(mon, 4, sleep_s=0.005)
        )
        from_trace = collections.Counter()
        for name, sec in events:
            from_trace[name[len("monitor."):]] += sec
        from_monitor = collections.Counter()
        for record in mon.recorder.history:
            for stage, sec in record.durations.items():
                if not stage.endswith("other_cpu_wall"):
                    from_monitor[stage] += sec
        assert set(from_trace) == set(from_monitor)
        for stage, sec in from_monitor.items():
            # the annotation opens just before the timed body and closes
            # just after it: the extents differ by the annotation's cost
            assert from_trace[stage] >= sec - 1e-5, stage
            assert from_trace[stage] - sec < 1e-3 + 0.01 * sec, stage


# -- the Monitor's own counts -----------------------------------------------


class TestMonitorCounts:
    def test_record_ns_grows_and_excludes_stage_bodies(self):
        mon = Monitor(fused_schema(world_size=1), window_steps=5)
        seen = []
        for _ in range(3):
            monitored_steps(mon, 4, sleep_s=0.01)
            seen.append(mon.metrics.counter("monitor.record_ns").value)
        assert 0 < seen[0] < seen[1] < seen[2]
        # 12 steps slept 120 ms inside stages; none of it is the Monitor's
        assert seen[-1] < 0.25 * 12 * 0.01 * 1e9
        assert seen[-1] >= mon.recorder.own_ns > 0

    def test_window_close_is_monitor_path_seconds(self):
        mon = Monitor(fused_schema(world_size=1), window_steps=3)
        monitored_steps(mon, 10)
        counters = mon.metrics.counters()
        assert counters["monitor.window_close_ns"] > 0
        assert counters["monitor.window_close_ns"] / 1e9 == mon.monitor_path_seconds
        assert counters["monitor.steps"] == 10
        assert counters["monitor.windows"] == 3
        assert mon.overhead_fraction(2.0) == mon.monitor_path_seconds / 2.0
        total = counters["monitor.record_ns"] + counters["monitor.window_close_ns"]
        assert mon.total_overhead_fraction(2.0) == total / 1e9 / 2.0
        assert mon.total_overhead_fraction(2.0) > mon.overhead_fraction(2.0)

    def test_event_channel_counts(self):
        mon = Monitor(fused_schema(world_size=1), window_steps=4, event_q=1.0)
        step = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((8,))
        for _ in range(6):
            with mon.step():
                with mon.stage("step.dispatch_cpu_wall"):
                    x = step(x)
                mon.observe_output(x, 0.1)
                with mon.stage("step.device_wait_cpu_wall"):
                    x.block_until_ready()
            mon.end_of_step()
        counters = mon.metrics.counters()
        hist = mon.metrics.histograms()["monitor.event_device_seconds"]
        assert counters["monitor.event_attempts"] == mon.events.attempts == 6
        assert counters["monitor.event_dropped"] == mon.events.dropped == 0
        assert hist.count == len(mon.events.samples) == 6
        want = sum(round(ms * 1e-3 * 1e9) for _, ms, _ in mon.events.samples)
        assert hist.sum_ns == want

    def test_two_monitors_merge_exactly(self):
        a = Monitor(fused_schema(world_size=1), window_steps=3, event_q=1.0)
        b = Monitor(fused_schema(world_size=1), window_steps=4, event_q=1.0)
        monitored_steps(a, 7)
        monitored_steps(b, 9)
        ab = merge_registries([a.metrics, b.metrics])
        assert ab.as_dict() == merge_registries([b.metrics, a.metrics]).as_dict()
        ca, cb = a.metrics.counters(), b.metrics.counters()
        assert ab.counters() == {k: ca[k] + cb[k] for k in ca}
        assert ab.counters()["monitor.windows"] == 2 + 2
        assert ab.counters()["monitor.steps"] == 16

    def test_no_step_recorded_costs_nothing(self):
        mon = Monitor(fused_schema(world_size=1))
        assert mon.end_of_step() is None
        assert mon.metrics.counters()["monitor.steps"] == 0
        assert mon.total_overhead_fraction(1.0) == 0.0


def test_train_summary_carries_the_monitor_counts():
    from repro.launch.train import make_argparser, run

    args = make_argparser().parse_args([
        "--arch", "paper-gpt-125m", "--reduced", "--steps", "6",
        "--batch", "2", "--seq", "32", "--window", "3", "--log-every", "1000",
    ])
    summary = run(args)
    counters = summary["monitor_metrics"]["counters"]
    assert counters["monitor.steps"] == 6 and counters["monitor.windows"] == 2
    assert summary["monitor_total_overhead"] >= summary["monitor_overhead"] > 0.0
    assert summary["monitor_overhead"] == pytest.approx(
        counters["monitor.window_close_ns"] / 1e9 / summary["train_seconds"]
    )


# -- tick.kernel holds the device wait ----------------------------------------


class _Late:
    """A kernel output that is ready only once someone waits for it."""

    def __init__(self, value):
        self.value, self.ready = value, False

    def block_until_ready(self):
        self.ready = True
        return self

    def __array__(self, dtype=None, copy=None):
        self.ready = True
        return np.asarray(self.value, dtype=dtype)


def _window_packet(d: np.ndarray) -> EvidencePacket:
    return EvidencePacket(
        window_index=0, schema_hash="schema-4", stages=("a", "b", "c", "d"),
        steps=d.shape[0], world_size=d.shape[1], gather_ok=True, labels=(),
        routing_stages=(), shares=(), gains=(), co_critical_stages=(),
        downgrade_reasons=(), leader_rank=-1, sync_stages=("b",), window=d,
    )


@pytest.mark.parametrize("fused", [True, False])
def test_kernel_outputs_ready_when_tick_kernel_closes(monkeypatch, fused):
    import repro.kernels.frontier as frontier

    name = "fused_fleet_tick" if fused else "four_dispatch_tick"
    real = getattr(frontier, name)
    outputs = []

    def slow_to_finish(*args, **kwargs):
        tick = real(*args, **kwargs)
        late = _Late(tick.frontier.shares)
        outputs.append((late, tick))
        return tick._replace(frontier=tick.frontier._replace(shares=late))

    monkeypatch.setattr(frontier, name, slow_to_finish)
    svc = FleetService(fused=fused)
    at_close = []
    phase = svc._phase

    @contextlib.contextmanager
    def watch(phase_name):
        with phase(phase_name):
            yield
            if phase_name == "tick.kernel":
                late, tick = outputs[-1]
                arrays = [x for x in jax.tree.leaves(tick) if isinstance(x, jax.Array)]
                at_close.append(late.ready and all(x.is_ready() for x in arrays))

    monkeypatch.setattr(svc, "_phase", watch)
    rng = np.random.default_rng(3)
    for j in range(3):
        svc.registry.update(f"j{j}", _window_packet(rng.exponential(0.1, (5, 4, 4))), 0)
    assert svc.refresh_batched() == 3
    assert at_close == [True]
    vec = svc.obs.tickline.recorder._cur
    assert vec["tick.kernel"] > 0.0 and vec["tick.epilog"] > 0.0
