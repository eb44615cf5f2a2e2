"""Each per-layer reader reads its own driver's traced run, returns nothing
for another driver's, and never reads a share of a peak or a roofline as
0 or above 100.

A reader of inputs that the samples below do not carry (a key of the
train step's `step_metrics`, an op's device time in `trace.op_s`) brings
its own: `data/layer_inputs/<reader>.json`, merged over every driver's
sample, its `trace` key over the trace summary's fields."""
import dataclasses
import json
from pathlib import Path

import pytest

import harness
import trace_reduce

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def traced(driver: str, extra: dict | None = None) -> dict:
    """A traced run's inputs as `run.measure` hands them to the readers,
    with a reader's own sample `extra` merged over them."""
    extra = dict(extra or {})
    summary = trace_reduce.TraceSummary(
        window_s=30.0, devices=1, busy_s=0.3,
        module_s={"jit__fused_tick_impl": 0.06, "jit_train_step": 29.9},
        op_s={"fusion": 9.2, "while": 25.3})
    summary = dataclasses.replace(summary, **extra.pop("trace", {}))
    common = {"driver": driver, "trace": summary, "peak": PEAK, "chips": 1}
    if driver == "train":
        common.update(tokens_per_s=53641.0, flops_per_token=8.6e8,
                      monitor_overhead_fraction=0.0008,
                      step_metrics={})
    elif driver == "fleet":
        common.update(ingest_s=2.5, ingest_windows=2000,
                      tick_flops=16 * 520 * 30 * 20 * 128 * 6.0,
                      tick_bytes=4 * 520 * 30 * 20 * 128 * 6.0)
    return {**common, **extra}


def reader_drivers(root: Path | None = None):
    """Every reader under `bench/layer_metrics/`, with the drivers of the cells
    that report it (None for a reader whose metric is not in the manifest yet)."""
    root = root or harness.ROOT
    m = harness.manifest(root)
    cells = {w["name"]: w for w in m["workloads"]}
    entries = {e["name"]: e for e in m["per_layer"]}
    for path in sorted((root / "bench" / "layer_metrics").glob("*.py")):
        entry = entries.get(path.stem)
        drivers = None if entry is None else {
            harness.load_json("configs", cells[c]["config"], root / "bench")["driver"]
            for c in entry.get("workloads", cells)}
        yield path.stem, drivers


def check_reader(name: str, drivers: set | None, root: Path | None = None) -> None:
    """The reader test's rule for reader `name` of the benchmark at `root`."""
    root = root or harness.ROOT
    reader = harness.load_module("layer_metrics", name, root / "bench")
    sample = root / "tests" / "bench" / "data" / "layer_inputs" / f"{name}.json"
    extra = json.loads(sample.read_text()) if sample.is_file() else {}
    read = {d: reader.read(traced(d, extra)) for d in {"train", "fleet"} | (drivers or set())}
    if drivers is not None:
        assert {d for d, v in read.items() if v is not None} == drivers
    assert any(v is not None for v in read.values())
    for value in read.values():
        if value is None:
            continue
        assert isinstance(value, float) and value > 0.0
        if name.endswith(("_pct", "_roofline")) or "mfu" in name:
            assert value <= 100.0, name


@pytest.mark.parametrize("name,drivers", list(reader_drivers()),
                         ids=[n for n, _ in reader_drivers()])
def test_reader_reads_its_own_driver_only(name, drivers):
    check_reader(name, drivers)
