"""BENCHMARK.json against the files it names, the contract's limits on its
entries, and a new cell picked up from files alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], bench)
        harness.load_module("drivers", cell.traffic["driver"])
        harness.load_module("reference", cell.config["reference"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_module("metrics", m["name"]).read)
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_names_units_and_bounds_keep_to_the_contract(bench):
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_a_new_cell_is_found_by_name_from_files_alone(bench, tmp_path,
                                                      monkeypatch):
    """A later change adds a traffic file, a limits file and a metric
    module, and names them in BENCHMARK.json; the harness finds them."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    base = bench["workloads"][0]
    traffic = harness.load_json(root / "bench" / "traffic"
                                / f"{base['traffic']}.json")
    batch = traffic["batch"]
    traffic["batch"] = 2 * batch
    (root / "bench" / "traffic" / "wide_batch.json").write_text(
        json.dumps(traffic))
    shutil.copy(root / "bench" / "limits" / f"{base['name']}.json",
                root / "bench" / "limits" / "new.cell.json")
    (root / "bench" / "metrics" / "units_in_window.py").write_text(
        "def read(rec):\n    return len(rec['window']['units'])\n")
    new = dict(bench, workloads=bench["workloads"] + [
        dict(base, name="new.cell", traffic="wide_batch")],
        per_layer=bench["per_layer"] + [
            {"name": "units_in_window", "unit": "units", "better": "higher",
             "source": "host_clock", "layer": "device",
             "moves": "train_samples_per_s", "workloads": ["new.cell"]}])
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    monkeypatch.setattr(harness, "ROOT", root)
    cell = harness.resolve("new.cell", new)
    assert cell.traffic["batch"] == 2 * batch
    assert "units_in_window" in [m["name"] for m in cell.per_layer]
    reader = harness.load_module("metrics", "units_in_window")
    assert reader.read({"window": {"units": [1.0, 2.0]}}) == 2


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "granite-8b.silo_train", "--seed", "1",
         "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [line for line in p.stdout.splitlines()
                if line.startswith("{")]


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A directory with BENCHMARK.json and bench/ alone has no program."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-8b.silo_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert not [line for line in p.stdout.splitlines()
                if line.startswith("{")]
