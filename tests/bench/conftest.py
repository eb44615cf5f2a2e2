"""Shared set-up for the benchmark's own tests: `bench/` and `src/` on the
path, and a run driven on the CPU at a size a test can hold."""
from __future__ import annotations

import copy
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: paper-gpt-125m's shapes cut to CPU size (widths too: a test, not a cell)
TINY_GPT = {"n_layer": 2, "n_head": 4, "n_embd": 128, "n_inner": 512,
            "vocab_size": 512, "block_size": 64, "bias": True,
            "rope_theta": 10000.0, "ln_eps": 1e-6}
TINY_TRAIN_TRAFFIC = {"batch": 4, "seq": 64, "monitor_window": 4}


def tiny_train_config() -> dict:
    cfg = json.loads((ROOT / "bench/configs/paper-gpt-125m.json").read_text())
    cfg["model"] = dict(TINY_GPT)
    return cfg


def cpu_context(config: dict, traffic: dict, *, seed: int = 7, seconds: float = 0.5,
                cell: str = "test-cell"):
    """A driver Context on the CPU: the harness's look for a chip skipped."""
    import jax

    import harness
    import run

    return run.Context(
        cell={"name": cell, "chips": 1}, config=copy.deepcopy(config),
        traffic=copy.deepcopy(traffic), seed=seed, seconds=seconds, trace=False,
        devices=jax.devices()[:1], clock=harness.Clock(0.0),
        compiles=harness.CompileCounter(jax), trace_dir="",
    )


@pytest.fixture
def tiny_train():
    return tiny_train_config(), dict(TINY_TRAIN_TRAFFIC)


#: fleet-ddp128's shapes cut to CPU size (the Pallas tick runs interpreted)
TINY_FLEET = {"jobs": 9, "ranks": 8, "sample_jobs": 6}
TINY_FLEET_TRAFFIC = {"rate_windows_per_s": 30, "route_k": 10}

#: the fleet cells as their manifest entries will read: driver, configurations and
#: traffic are in `bench/`; they enter BENCHMARK.json once their bounds are measured
#: on the chip, and until then the tests add them to their copy of the manifest
FLEET_CELLS = [
    {"name": "fleet-ddp128-steady", "config": "fleet-ddp128", "traffic": "ddp128-steady",
     "chips": 1, "why": "a test"},
    {"name": "fleet-pai8-steady", "config": "fleet-pai8", "traffic": "pai8-steady",
     "chips": 1, "why": "a test"},
]
FLEET_METRICS = [
    {"name": "route_p95_ms", "unit": "ms", "better": "lower", "source": "host_clock"},
    {"name": "routed_windows_per_s", "unit": "windows/s", "better": "higher",
     "source": "host_clock"},
]

#: limits for the CPU-sized runs, from CPU readings at these sizes on
#: seeds 21-23 (the cells' own limits are set from chip readings at the
#: cells' sizes).  The bf16 program against the reference read loss
#: 0.00066-0.00078, gradient 0.00075-0.00126, change 0.0023-0.0075; the
#: float8 control's gradient 0.011-0.017; the half-batch fault's loss
#: 0.016-0.029, gradient 0.16-0.18, change 0.068-0.083.  The kernel reads
#: 2e-7 against the oracle, the bfloat16 oracle 1.0.
TINY_LIMITS = {
    "train": {"loss_gap": 0.005, "grad_gap": 0.005, "change_gap": 0.04},
    "fleet": {"kernel_gap": 1e-4, "leader_mismatch": 0, "route_misses": 0},
}


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A copy of the benchmark whose cells run at CPU size, with the
    harness pointed at it and its look for a chip skipped: returns a
    function that runs one cell through `run.measure` and gives back the
    result object."""
    import shutil

    import harness
    import run

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = tmp_path / "bench"
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    missing = [c for c in FLEET_CELLS if c["name"] not in {w["name"] for w in m["workloads"]}]
    if missing:
        m["workloads"] += missing
        m["end_to_end"] += [dict(e, workloads=[c["name"] for c in missing])
                            for e in FLEET_METRICS]
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    for w in m["workloads"]:
        cfg_path = bench / "configs" / f"{w['config']}.json"
        traffic_path = bench / "traffic" / f"{w['traffic']}.json"
        cfg = json.loads(cfg_path.read_text())
        if cfg["driver"] == "train":
            cfg["model"] = dict(TINY_GPT)
            traffic = dict(TINY_TRAIN_TRAFFIC)
        else:
            cfg.update(TINY_FLEET)
            traffic = dict(TINY_FLEET_TRAFFIC)
        cfg["limits"] = dict(TINY_LIMITS[cfg["driver"]])
        cfg_path.write_text(json.dumps(cfg))
        traffic_path.write_text(json.dumps(traffic))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "")

    def measure(workload, *, seed=5, seconds=1.0, trace=0):
        import jax

        args = run.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.measure(args, devices=jax.devices()[:1],
                           peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})

    return measure
