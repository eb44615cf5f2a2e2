#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --list

The cell is an entry of `workloads` in BENCHMARK.json; its configuration,
traffic and per-layer metric readers are found by name under `bench/`
(see `harness.py`).  Set-up (imports, device start, weights or packets,
compilation, warm-up) runs first and is reported as `setup_s`; then the
window measures for `--seconds`; then what the window produced is checked
against the plain reference.  With `--trace 1` a profiler trace covers the
window and the result carries the cell's per-layer metrics instead of its
end-to-end ones.

The last line of standard output is the result object; the numbers that
decide `correct` are also the last lines of standard error.  Without a TPU,
with fewer chips than the cell asks for, or on a device missing from
`bench/peaks.json`, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, its seed and window, the
    devices, and the harness's clock, compile counter and tracer."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    clock: harness.Clock
    compiles: harness.CompileCounter
    trace_dir: str

    @contextlib.contextmanager
    def window(self):
        """Around the measured window: with `--trace 1` the profiler runs
        and the window carries the `bench.window` annotation."""
        if not self.trace:
            yield contextlib.nullcontext()
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield jax.profiler.TraceAnnotation("bench.window")
        finally:
            jax.profiler.stop_trace()


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--list", action="store_true",
                   help="print the cells and metric readers the harness finds")
    return p.parse_args(argv)


def measure(args, *, devices=None, peak=None) -> dict:
    """One run; returns the result object it printed.  `devices` and
    `peak` skip the look for a chip (the tests drive a run on the CPU)."""
    cell = harness.workload(args.workload)
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    driver = harness.load_module("drivers", config["driver"])
    seconds = args.seconds if args.seconds is not None else harness.manifest()["run_seconds"]
    e2e, per_layer = harness.cell_metrics(cell["name"])
    readers = {m["name"]: harness.load_module("layer_metrics", m["name"]) for m in per_layer}

    import jax

    cache = harness.use_compile_cache(jax)
    devs = devices or harness.check_devices(jax, cell["chips"])
    peak = peak or harness.peaks(devs[0].device_kind)
    compiles = harness.CompileCounter(jax)
    harness.say("device", platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), compile_cache=cache)
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), devices=devs, clock=harness.Clock(T_START),
        compiles=compiles,
        trace_dir=str(harness.ROOT / "bench_out" / "trace" / cell["name"]),
    )
    out = driver.run(ctx)
    if out["compiles_in_window"]:
        harness.say("warning", compiles_in_window=out["compiles_in_window"])
    harness.say("compile", programs=compiles.programs, cache_hits=compiles.cache_hits,
                seconds=compiles.seconds)
    device = harness.device_line(devs, out["memory_peak_bytes"])
    breakdown = None
    if ctx.trace:
        import trace_reduce

        summary = trace_reduce.reduce_trace(trace_reduce.find_xplane(ctx.trace_dir))
        harness.say("trace", window_s=summary.window_s, busy_s=summary.busy_s,
                    modules=summary.module_s, calls=summary.module_calls,
                    idle_by_label=summary.idle_by_label)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
        inputs = {**out["layer"], "driver": config["driver"], "trace": summary,
                  "peak": peak, "chips": len(devs)}
        metrics = {}
        for m in per_layer:
            value = readers[m["name"]].read(inputs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**out["end_to_end"], "setup_s": out["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in e2e}
    checks = out["checks"]
    correct = all(c["ok"] for c in checks.values())
    harness.print_checks(checks)
    line = harness.result_line(correct=correct, attempted=out["attempted"],
                               failed=out["failed"], metrics=metrics, device=device,
                               breakdown=breakdown, checks=checks)
    print(line, flush=True)
    return json.loads(line)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        if args.list:
            print(json.dumps(harness.listing(), indent=1))
            return 0
        if not args.workload:
            raise harness.BenchError("--workload is required")
        measure(args)
        return 0
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
