#!/usr/bin/env python3
"""Find a fleet cell's knee once, by a sweep of offered rates on the chip.

    python bench/knee.py --workload <cell> --rates 100,200,400 [--seconds 8]

Runs the cell's driver at each offered rate (windows per second) in one
process and prints, per rate, the windows routed per second inside the
window and the tail of their latency.  The knee is the highest rate the
service sustains: routed keeps up with offered and the tail stays within
a few periods.  A cell's traffic file then fixes its rate at about four
fifths of it; the cells never search for a rate themselves.
"""
from __future__ import annotations

import time

import argparse
import copy
import json
import sys

import run  # noqa: F401  (puts bench/ and src/ on the path)
import harness


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=1)
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
        print(f"knee: {e}", file=sys.stderr)
        return 2
    compiles = harness.CompileCounter(jax)
    rows = []
    for rate in [float(r) for r in a.rates.split(",")]:
        t = copy.deepcopy(traffic)
        t["rate_windows_per_s"] = rate
        ctx = run.Context(cell=cell, config=config, traffic=t, seed=a.seed,
                          seconds=a.seconds, trace=False, devices=devs,
                          clock=harness.Clock(time.perf_counter()), compiles=compiles,
                          trace_dir="")
        out = driver.run(ctx)
        row = {**out["end_to_end"], **out["diagnostics"], "setup_s": out["setup_s"]}
        rows.append(row)
        harness.say("knee", **row)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
