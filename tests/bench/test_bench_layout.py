"""The harness finds cells, configurations, traffic and per-layer metrics
by name: adding files and entries adds them, with no harness file edited."""
import json
import shutil
import subprocess
import sys

import harness
from conftest import ROOT


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


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    root = copy_bench(tmp_path)
    before = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/configs/gpt-dummy.json").write_text(json.dumps({"driver": "train"}))
    (root / "bench/traffic/dummy-mix.json").write_text(json.dumps({"batch": 1}))
    (root / "bench/layer_metrics/dummy.share.py").write_text(
        "def read(run):\n    return None\n")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "dummy-cell", "config": "gpt-dummy",
                           "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    m["per_layer"].append({"name": "dummy.share", "unit": "%", "better": "higher",
                           "source": "device_trace", "layer": "device",
                           "moves": "tokens_per_s", "workloads": ["dummy-cell"]})
    m["end_to_end"][0]["workloads"].append("dummy-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    out = harness.listing(root)
    assert out["cells"]["dummy-cell"]["driver"] == "train"
    assert out["cells"]["dummy-cell"]["per_layer"] == ["dummy.share"]
    assert "dummy.share" in out["readers"]
    after = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*")
             if p.is_file() and p.relative_to(root) in before}
    assert after == before
    reader = harness.load_module("layer_metrics", "dummy.share", root / "bench")
    assert reader.read({}) is None


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
