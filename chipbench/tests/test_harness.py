"""The harness: cells resolved by name from files, a whole run rehearsed
on the CPU at a small size (the last line's shape, and no device metric
from a CPU), and no result without a TPU."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench import harness
from chipbench.tests import rehearsal

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cells_resolve_from_their_files(name):
    cell = harness.load_cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.chips == entry["chips"]
    assert cell.spec["name"] == entry["config"]
    for kind in ("reference", "cost", "program"):
        assert cell.module(kind).__name__ == f"chipbench.{kind}.{cell.arch}"
    assert {"rate_wps", "solo", "check"} <= set(cell.params)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end + cell.per_layer:
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        assert callable(mod.read)


def test_every_metric_and_config_has_its_file():
    for c in BENCH["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"] and spec["reduced"] == c["reduced"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def run(root, name, seconds=3.0, traced=False, faults=None):
    cell = harness.load_cell(name, root)
    devices = [jax.devices()[0]] * cell.chips
    return harness.run_cell(cell, 2 ** 31 + 99, seconds, traced, devices,
                            0.0, log=lambda s: None, faults=faults)


@pytest.mark.parametrize("name,traced", [
    (CELLS[0], False), (CELLS[1], True), (rehearsal.REPLICAS, False)])
def test_rehearsal_prints_a_correct_line_and_no_device_metric(
        small_root, name, traced):
    res = run(small_root, name, traced=traced)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True and res["attempted"] > 0
    assert res["failed"] == 0
    assert res["metrics"] == {}                 # a CPU run reports none
    assert res["device"]["platform"] == "cpu"
    assert set(res["check"]) == {"gap_mean", "wrong_length", "out_of_vocab"}
    for v in res["check"].values():
        assert v["value"] <= v["limit"]
    json.loads(json.dumps(res))


def test_no_tpu_no_result(tmp_path):
    """run.py exits non-zero and prints nothing on stdout without a TPU,
    and also from a directory holding only the benchmark's own files."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0"]
    for root in (ROOT, tmp_path):
        if root == tmp_path:
            shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
            shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                            ignore=shutil.ignore_patterns("__pycache__"))
        p = subprocess.run([sys.executable, "chipbench/run.py", *args],
                           cwd=root, env=env, capture_output=True,
                           text=True, timeout=120)
        assert p.returncode != 0 and p.stdout == ""
