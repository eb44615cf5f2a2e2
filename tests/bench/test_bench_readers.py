"""Each per-layer reader reads its own driver's traced run, returns nothing
for another driver's, and never reads a share of a peak or a roofline as
0 or above 100."""
import pytest

import harness
import trace_reduce

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def traced(driver: str) -> dict:
    """A traced run's inputs as `run.measure` hands them to the readers."""
    summary = trace_reduce.TraceSummary(
        window_s=30.0, devices=1, busy_s=0.3,
        module_s={"jit__fused_tick_impl": 0.06, "jit_train_step": 29.9})
    common = {"driver": driver, "trace": summary, "peak": PEAK, "chips": 1}
    if driver == "train":
        return {**common, "tokens_per_s": 53641.0, "flops_per_token": 8.6e8,
                "monitor_overhead_fraction": 0.0008}
    return {**common, "ingest_s": 2.5, "ingest_windows": 2000,
            "tick_flops": 16 * 520 * 30 * 20 * 128 * 6.0,
            "tick_bytes": 4 * 520 * 30 * 20 * 128 * 6.0}


def reader_drivers():
    """Every reader under `bench/layer_metrics/`, with the drivers of the cells
    that report it (None for a reader whose metric is not in the manifest yet)."""
    m = harness.manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    entries = {e["name"]: e for e in m["per_layer"]}
    for path in sorted((harness.BENCH / "layer_metrics").glob("*.py")):
        entry = entries.get(path.stem)
        drivers = None if entry is None else {
            harness.load_json("configs", cells[c]["config"])["driver"]
            for c in entry.get("workloads", cells)}
        yield path.stem, drivers


@pytest.mark.parametrize("name,drivers", list(reader_drivers()),
                         ids=[n for n, _ in reader_drivers()])
def test_reader_reads_its_own_driver_only(name, drivers):
    reader = harness.load_module("layer_metrics", name)
    read = {d: reader.read(traced(d)) for d in ("train", "fleet")}
    if drivers is not None:
        assert {d for d, v in read.items() if v is not None} == drivers & set(read)
    assert any(v is not None for v in read.values())
    for value in read.values():
        if value is None:
            continue
        assert isinstance(value, float) and value > 0.0
        if name.endswith(("_pct", "_roofline")):
            assert value <= 100.0, name
