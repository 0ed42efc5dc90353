"""The command's contract that needs no chip: where it refuses to run,
and that every name in BENCHMARK.json finds its files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _run(cwd, workload=BENCH["workloads"][0]["name"]):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(harness.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_metrics(cell):
    wl, centry, config, traffic = harness.find_cell(BENCH, cell)
    assert config["name"] == wl["config"] == centry["name"]
    assert (harness.HERE / "drivers" / f"{traffic['driver']}.py").exists()
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.cell_metrics(BENCH, cell, True)
    assert layer
    for m in layer:
        assert callable(harness.reader(m["name"]).read)
        assert m["moves"] in e2e


def test_config_reduced_keys_match_benchmark():
    for c in BENCH["configs"]:
        config = json.loads((harness.ROOT / c["file"]).read_text())
        assert sorted(config["reduced"]) == sorted(c["reduced"])


def test_idle_share_reads_100_when_nothing_ran_and_nothing_untraced():
    idle = harness.reader("device_idle_share.decode")
    assert idle.read(harness.Run({}, {}, [], {"busy_s": 0.0,
                                               "window_s": 2.0}, {})) == 100
    assert idle.read(harness.Run({}, {}, [], {"busy_s": 0.5,
                                               "window_s": 2.0}, {})) == 75
    assert idle.read(harness.Run({}, {}, [], None, {})) is None
