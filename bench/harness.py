"""What every cell shares: finding a cell's files by name, the device
checks, the compile counter, the traced window and the result line.

A cell is one entry of `workloads` in BENCHMARK.json.  Its files:

- `bench/configs/<config>.json`, the configuration as it is run; its
  `driver` names `bench/drivers/<driver>.py`, and a `train`
  configuration's `reference` names `bench/<reference>.py`, the plain
  reference of its model, with the functions `drivers/train.py` lists;
- `bench/traffic/<traffic>.json`, the traffic mix;
- `bench/layer_metrics/<metric>.py` for each per-layer metric, whose
  `read(run)` takes the driver's `layer` readings (a train run's
  `step_metrics`, each non-loss key of the step's `metrics` by step, among
  them) and the trace summary (`trace_reduce.TraceSummary`, with `op_s`,
  the device time of every op by name), and returns None where it finds
  nothing to read;
- `tests/bench/tiny/<config>.json`, the configuration's cut to CPU size
  for the benchmark's own tests.

A later change adds a configuration, a traffic mix or a metric by adding
such files and entries: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that must end without a result (exit code 2)."""


# -- finding things by name ---------------------------------------------------


def manifest(root: Path | None = None) -> dict:
    return json.loads(((root or ROOT) / "BENCHMARK.json").read_text())


def workload(name: str, root: Path | None = None) -> dict:
    for w in manifest(root)["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, bench: Path | None = None) -> dict:
    bench = bench or BENCH
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} file {path.relative_to(bench.parent)}")
    return json.loads(path.read_text())


def _exec(path: Path, module_name: str):
    """The file `path` run as a module named `module_name`."""
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _module_name(*parts: str) -> str:
    return "_".join(["bench", *parts]).replace(".", "_").replace("-", "_")


def load_module(kind: str, name: str, bench: Path | None = None):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    bench = bench or BENCH
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path.relative_to(bench.parent)}")
    return _exec(path, _module_name(kind, name))


def reference(name: str, bench: Path | None = None):
    """The plain reference module `bench/<name>.py` that a configuration
    names, loaded from that file."""
    bench = bench or BENCH
    path = bench / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reference module {path.relative_to(bench.parent)}")
    return _exec(path, _module_name("reference", name))


def cell_metrics(cell: str, root: Path | None = None) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metric entries that `cell` reports."""
    m = manifest(root)

    def mine(entry):
        return "workloads" not in entry or cell in entry["workloads"]

    return (
        [e for e in m["end_to_end"] if mine(e)],
        [e for e in m["per_layer"] if mine(e)],
    )


def listing(root: Path | None = None) -> dict:
    """Every cell with its configuration, traffic, driver and metrics, and
    every per-layer metric with its reader: what the harness can run."""
    root = root or ROOT
    m = manifest(root)
    bench = root / "bench"
    cells = {}
    for w in m["workloads"]:
        cfg = load_json("configs", w["config"], bench)
        load_json("traffic", w["traffic"], bench)
        e2e, layer = cell_metrics(w["name"], root)
        cells[w["name"]] = {
            "driver": cfg["driver"],
            "end_to_end": [e["name"] for e in e2e],
            "per_layer": [e["name"] for e in layer],
        }
    readers = {}
    for e in m["per_layer"]:
        load_module("layer_metrics", e["name"], bench)
        readers[e["name"]] = str(Path("bench/layer_metrics") / f"{e['name']}.py")
    return {"cells": cells, "readers": readers}


# -- devices ------------------------------------------------------------------


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def check_devices(jax, chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def device_line(devs, memory_peak_bytes: int) -> dict:
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(memory_peak_bytes),
    }


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of `devs`, as the backend says."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


def use_compile_cache(jax) -> str:
    """JAX's persistent cache in `.jax_cache` of the checkout, whatever the
    environment names: a fixed path (the path is part of the cache key),
    with no size cap, holding every program however fast it compiled, so
    that only a checkout's first run of a cell compiles."""
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs JAX compiled or loaded from its cache, from its own events
    (one backend-compile event per program, a cache hit or not)."""

    def __init__(self, jax):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# -- statistics -----------------------------------------------------------------


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = 0.95 * (len(xs) - 1)
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


# -- the result line --------------------------------------------------------------


def say(tag: str, **fields) -> None:
    print(f"[{tag}] " + json.dumps(fields, default=str), flush=True)


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None, checks: dict) -> str:
    out: dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()


def check(value: float, limit: float) -> dict:
    """A compared number against its limit; a missing answer reads 1e300."""
    value = float(value)
    if not math.isfinite(value):
        value = 1e300
    return {"value": value, "limit": limit, "ok": value <= limit}


class Clock:
    """Seconds since the process started (set-up's zero)."""

    def __init__(self, t0: float):
        self.t0 = t0

    def now(self) -> float:
        return time.perf_counter() - self.t0
