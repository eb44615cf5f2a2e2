#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python bench/readings.py --workload <cell> --seeds 1,2,3 [--seconds 2]
                             [--control-seeds 1,2,3] [--out <file.json>]

For each seed, one run of the cell as `run.py` makes it (a short window),
giving the program's readings of every compared number; for each control
seed, the driver's control (the reference one precision below the
configuration's, put in the program's place) and its planted faults.  All
in one process, so that the compiled programs are shared.  The cell's own
runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (puts bench/ and src/ on the path)
import harness  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", default="")
    a = p.parse_args()
    cell = harness.workload(a.workload)
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    driver = harness.load_module("drivers", config["driver"])

    import jax

    harness.use_compile_cache(jax)
    try:
        devs = harness.check_devices(jax, cell["chips"])
    except harness.BenchError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    compiles = harness.CompileCounter(jax)
    out = {"workload": a.workload, "program": {}, "control": {}}

    def ctx(seed):
        return run.Context(cell=cell, config=config, traffic=traffic, seed=seed,
                           seconds=a.seconds, trace=False, devices=devs,
                           clock=harness.Clock(time.perf_counter()), compiles=compiles,
                           trace_dir="")

    for seed in [int(s) for s in a.seeds.split(",") if s]:
        r = driver.run(ctx(seed))
        out["program"][seed] = {k: c["value"] for k, c in r["checks"].items()}
        harness.say("program", seed=seed, **out["program"][seed])
    for seed in [int(s) for s in a.control_seeds.split(",") if s]:
        out["control"][seed] = driver.control(ctx(seed))
        harness.say("control", seed=seed, **out["control"][seed])
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
