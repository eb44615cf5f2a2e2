"""From a profiler trace (`.xplane.pb`) to device busy and idle time,
device time per compiled module and per operation, the heaviest device
operations and the longest idle gaps, each gap named by the host
annotation it fell in.

Device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event
per operation run on the chip, their `XLA Modules` line one per program
execution (named `jit_<fn>(<hash>)`).  Host annotations
(`jax.profiler.TraceAnnotation`) are events of the `/host:CPU` plane.
The traced window is the host annotation `WINDOW`; device events are
clipped to it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HASH = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"\.\d+$")


@dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                     # mean over devices of the busy union
    module_s: dict = field(default_factory=dict)    # module -> device s (sum over devices)
    module_calls: dict = field(default_factory=dict)
    op_s: dict = field(default_factory=dict)        # op -> device s (sum over devices)
    top_ops: list = field(default_factory=list)     # [[op, seconds]]
    idle_gaps: list = field(default_factory=list)   # [[host annotation, seconds]]
    idle_by_label: dict = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """`jit__fused_tick_impl(5394...)` -> `jit__fused_tick_impl`."""
    return _HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion`."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def merge(intervals):
    """Sorted, disjoint union of [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_trace(path: str, *, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = []           # (name, start, end) of this harness's annotations
    devices = []        # (ops [(name, s, e)], modules [(name, s, e)])
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                dst = ops if line.name == "XLA Ops" else mods
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
            devices.append((ops, mods))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        spans = [(s, e) for ops, _ in devices for _, s, e in ops]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    labels = [(n, s, e) for n, s, e in host if n != WINDOW]

    busy_total = 0.0
    module_s: dict = {}
    module_calls: dict = {}
    op_s: dict = {}
    gaps = []
    for ops, mods in devices:
        clipped = []
        for name, s, e in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e))
                key = op_name(name)
                op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
        union = merge(clipped)
        busy_total += sum(e - s for s, e in union) * 1e-9
        for name, s, e in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + (e - s) * 1e-9
                module_calls[key] = module_calls.get(key, 0) + 1
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    idle_by_label: dict = {}
    named = []
    for s, e in gaps:
        best, best_overlap = "host.other", 0
        for n, hs, he in labels:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = n, overlap
        sec = (e - s) * 1e-9
        named.append([best, sec])
        idle_by_label[best] = idle_by_label.get(best, 0.0) + sec
    named.sort(key=lambda x: -x[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        devices=len(devices),
        busy_s=busy_total / len(devices),
        module_s=module_s,
        module_calls=module_calls,
        op_s=op_s,
        top_ops=sorted(([k, v] for k, v in op_s.items()), key=lambda x: -x[1])[:top],
        idle_gaps=named[:top],
        idle_by_label=idle_by_label,
    )
