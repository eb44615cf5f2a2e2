"""The harness finds cells, configurations, traffic and per-layer metrics
by name: adding files and entries adds them, with no file of the
benchmark or of its tests edited."""
import json
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import ROOT, make_tiny_bench, train_cells
from test_bench_readers import check_reader, reader_drivers


def copy_bench(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_listing_covers_every_cell_and_reader():
    out = harness.listing()
    m = harness.manifest()
    assert set(out["cells"]) == {w["name"] for w in m["workloads"]}
    assert set(out["readers"]) == {e["name"] for e in m["per_layer"]}


TWIN = "gpt-twin"            # the second train configuration
TWIN_CELL = "train-twin-b8s512"
TWIN_READER = "twin.grad_norm"   # a reader name may hold dots


def add_second_train_config(root, *, tiny: bool) -> None:
    """Add to the benchmark at `root`, by new files and entries alone, a
    second train configuration with its own reference module (a copy of
    `gpt_reference` under another name, at other widths), its traffic,
    its CPU size (where `tiny`), its cell in the train metrics'
    `workloads`, and a reader of one `step_metrics` key with its sample."""
    bench = root / "bench"
    shutil.copy(bench / "gpt_reference.py", bench / "twin_reference.py")
    cfg = json.loads((bench / "configs/paper-gpt-125m.json").read_text())
    cfg["reference"] = "twin_reference"
    cfg["model"].update(n_layer=24, n_head=16, n_embd=1024, n_inner=4096)
    (bench / "configs" / f"{TWIN}.json").write_text(json.dumps(cfg))
    (bench / "traffic/twin-b8s512.json").write_text(
        json.dumps({"batch": 8, "seq": 512, "monitor_window": 10}))
    if tiny:
        (root / "tests/bench/tiny" / f"{TWIN}.json").write_text(json.dumps({
            "config": {"model": {**cfg["model"], "n_layer": 3, "n_head": 2, "n_embd": 64,
                                 "n_inner": 256, "vocab_size": 384, "block_size": 32}},
            "traffic": {"batch": 4, "seq": 32, "monitor_window": 4},
            "limits": {"loss_gap": 0.005, "grad_gap": 0.005, "change_gap": 0.04}}))
    (bench / "layer_metrics" / f"{TWIN_READER}.py").write_text(
        "import statistics\n\n\n"
        "def read(run):\n"
        "    norms = run.get(\"step_metrics\", {}).get(\"grad_norm\")\n"
        "    if run.get(\"driver\") != \"train\" or not norms:\n"
        "        return None\n"
        "    return float(statistics.median(norms))\n")
    (root / "tests/bench/data/layer_inputs" / f"{TWIN_READER}.json").write_text(
        json.dumps({"step_metrics": {"grad_norm": [0.9, 0.8, 0.85]}}))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": TWIN, "source": "https://huggingface.co/gpt2-medium",
                         "file": f"bench/configs/{TWIN}.json", "reduced": [], "why": "a test"})
    m["workloads"].append({"name": TWIN_CELL, "config": TWIN, "traffic": "twin-b8s512",
                           "chips": 1, "why": "a test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ("tokens_per_s", "step_p95_ms", "train_mfu_pct",
                         "train_device_idle_pct"):
            e["workloads"].append(TWIN_CELL)
    m["per_layer"].append({"name": TWIN_READER, "unit": "1", "better": "lower",
                           "source": "program_counter", "layer": "train step",
                           "moves": "tokens_per_s", "workloads": [TWIN_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))


def fake_trace(monkeypatch):
    """The CPU's profiler trace has no TPU plane: hand the readers a summary."""
    import trace_reduce

    summary = trace_reduce.TraceSummary(window_s=1.0, devices=1, busy_s=0.5,
                                        op_s={"fusion": 0.4})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda trace_dir: trace_dir)
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda path: summary)


@pytest.mark.parametrize("tiny", [True, False], ids=["cpu_size", "no_cpu_size"])
def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path, monkeypatch, tiny):
    root = tmp_path / "src"
    copy_bench(root)
    shutil.copytree(ROOT / "tests/bench", root / "tests/bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests/bench/data/layer_inputs").mkdir(exist_ok=True)
    before = {p.relative_to(root): p.read_bytes()
              for d in ("bench", "tests/bench") for p in (root / d).rglob("*") if p.is_file()}
    add_second_train_config(root, tiny=tiny)
    out = harness.listing(root)
    assert out["cells"][TWIN_CELL]["driver"] == "train"
    assert out["cells"][TWIN_CELL]["end_to_end"] == ["tokens_per_s", "step_p95_ms", "setup_s"]
    assert out["cells"][TWIN_CELL]["per_layer"] == [
        "train_mfu_pct", "train_device_idle_pct", TWIN_READER]
    assert TWIN_READER in out["readers"]
    assert harness.load_module("layer_metrics", TWIN_READER, root / "bench").__name__ == (
        "bench_layer_metrics_twin_grad_norm")
    assert TWIN_CELL in train_cells(root)   # the train fault and control tests run it
    drivers = dict(reader_drivers(root))
    assert drivers[TWIN_READER] == {"train"}
    for name, readers_drivers in drivers.items():
        check_reader(name, readers_drivers, root)
    measure = make_tiny_bench(root, tmp_path / "run", monkeypatch)
    if tiny:
        got = measure(TWIN_CELL, seed=2**31 + 17)
        assert got["correct"], got["checks"]
        assert set(got["metrics"]) == {"tokens_per_s", "step_p95_ms", "setup_s"}
        fake_trace(monkeypatch)
        got = measure(TWIN_CELL, seed=2**31 + 18, trace=1)
        assert got["correct"], got["checks"]
        assert set(got["metrics"]) == {"train_mfu_pct", "train_device_idle_pct", TWIN_READER}
        assert got["metrics"][TWIN_READER]["value"] > 0.0
    else:
        with pytest.raises(pytest.fail.Exception, match=f"add tests/bench/tiny/{TWIN}.json"):
            measure(TWIN_CELL)
    after = {p: (root / p).read_bytes() for p in before}
    assert after == before


def test_a_missing_reference_module_is_named(tmp_path):
    with pytest.raises(harness.BenchError, match="bench/no_such_reference.py"):
        harness.reference("no_such_reference")
    ref = harness.reference("gpt_reference")
    assert ref.__file__ == str(ROOT / "bench/gpt_reference.py") and ref.train_flops_per_token
    # a name that another module on the path already has still loads the named file
    (tmp_path / "bench").mkdir()
    (tmp_path / "bench/trace_reduce.py").write_text("NAMED = True\n")
    assert harness.reference("trace_reduce", tmp_path / "bench").NAMED


def test_without_a_tpu_there_is_no_result(tmp_path):
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "train-gpt125m-b8s1k", "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
    )
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_the_benchmark_files_alone_give_no_result(tmp_path):
    root = copy_bench(tmp_path)
    shutil.copytree(ROOT / "tests/bench", root / "tests/bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-gpt125m-b8s1k",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=root,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
    )
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_manifest_keeps_to_the_contract():
    m = harness.manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [c["name"] for c in m["configs"]]
    cells = [w["name"] for w in m["workloads"]]
    e2e = [e["name"] for e in m["end_to_end"]]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert "setup_s" in e2e
    for w in m["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4) and len(w["why"]) <= 200
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] in ("host_clock", "device_trace")
    for e in m["per_layer"]:
        assert e["moves"] in e2e
        assert all(c in cells for c in e.get("workloads", cells))
