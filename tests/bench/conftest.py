"""Shared set-up for the benchmark's own tests: `bench/` and `src/` on the
path, and a run driven on the CPU at a size a test can hold.

Each configuration's CPU size is `tests/bench/tiny/<config>.json`: its
`config` keys replace the configuration's, its `traffic` keys the cell's
traffic's, and its `limits` the configuration's limits."""
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

TINY = "tests/bench/tiny"


def tiny_file(config: str, root: Path = ROOT) -> dict:
    """`config`'s cut to CPU size; fails, naming the file to add, where
    there is none."""
    path = root / TINY / f"{config}.json"
    if not path.is_file():
        pytest.fail(f"configuration {config!r} has no CPU size: add {TINY}/{config}.json "
                    "with the `config`, `traffic` and `limits` keys a test run takes")
    return json.loads(path.read_text())


_GPT = tiny_file("paper-gpt-125m")
_FLEET = tiny_file("fleet-ddp128")

#: paper-gpt-125m's shapes cut to CPU size (widths too: a test, not a cell)
TINY_GPT = _GPT["config"]["model"]
TINY_TRAIN_TRAFFIC = _GPT["traffic"]


def cut_to_cpu(config: dict, traffic: dict, cut: dict) -> tuple[dict, dict]:
    """A configuration and its traffic at the CPU size `cut` (a tiny file)."""
    return ({**config, **cut["config"], "limits": cut["limits"]},
            {**traffic, **cut["traffic"]})


def tiny_cell(cell: str, root: Path = ROOT) -> tuple[dict, dict]:
    """The configuration and the traffic of `cell` at their CPU size."""
    import harness

    w, bench = harness.workload(cell, root), root / "bench"
    return cut_to_cpu(harness.load_json("configs", w["config"], bench),
                      harness.load_json("traffic", w["traffic"], bench),
                      tiny_file(w["config"], root))


def train_cells(root: Path = ROOT) -> list[str]:
    """The cells in BENCHMARK.json whose configuration runs the `train` driver."""
    import harness

    return [w["name"] for w in harness.manifest(root)["workloads"]
            if harness.load_json("configs", w["config"], root / "bench")["driver"] == "train"]


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


#: fleet-ddp128's shapes cut to CPU size (the Pallas tick runs interpreted)
TINY_FLEET = _FLEET["config"]
TINY_FLEET_TRAFFIC = _FLEET["traffic"]

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

#: limits for the CPU-sized runs of paper-gpt-125m and fleet-ddp128, by
#: driver, from CPU readings at these sizes (each tiny file's `why`); the
#: cells' own limits are set from chip readings at the cells' sizes, and
#: each configuration's CPU limits are its tiny file's (`tiny_cell`)
TINY_LIMITS = {"train": _GPT["limits"], "fleet": _FLEET["limits"]}


def make_tiny_bench(src: Path, dst: Path, monkeypatch):
    """Copy the benchmark at `src` to `dst` with every cell cut to its
    configuration's CPU size (`src`'s tiny files), point the harness at
    the copy and skip its look for a chip: returns a function that runs
    one cell through `run.measure` and gives back the result object.  A
    cell whose configuration has no tiny file fails when it is run."""
    import shutil

    import harness
    import run

    shutil.copytree(src / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = dst / "bench"
    m = json.loads((src / "BENCHMARK.json").read_text())
    missing = [c for c in FLEET_CELLS if c["name"] not in {w["name"] for w in m["workloads"]}]
    if missing:
        m["workloads"] += missing
        m["end_to_end"] += [dict(e, workloads=[c["name"] for c in missing])
                            for e in FLEET_METRICS]
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    uncut = set()
    for w in m["workloads"]:
        if not (src / TINY / f"{w['config']}.json").is_file():
            uncut.add(w["name"])
            continue
        cfg_path = bench / "configs" / f"{w['config']}.json"
        traffic_path = bench / "traffic" / f"{w['traffic']}.json"
        cfg, traffic = cut_to_cpu(json.loads(cfg_path.read_text()),
                                  json.loads(traffic_path.read_text()),
                                  tiny_file(w["config"], src))
        cfg_path.write_text(json.dumps(cfg))
        traffic_path.write_text(json.dumps(traffic))
    monkeypatch.setattr(harness, "ROOT", dst)
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "")
    monkeypatch.syspath_prepend(str(bench))

    def measure(workload, *, seed=5, seconds=1.0, trace=0):
        import jax

        if workload in uncut:
            tiny_file(harness.workload(workload)["config"], src)
        args = run.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.measure(args, devices=jax.devices()[:1],
                           peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})

    return measure


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """The benchmark at CPU size (`make_tiny_bench` of this checkout)."""
    return make_tiny_bench(ROOT, tmp_path, monkeypatch)
