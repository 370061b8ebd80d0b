"""CPU rehearsals of every cell's runner at a tiny size, with the
program's Pallas kernels in interpret mode, and the entry point's refusal
to run without an accelerator."""
import os
import shutil
import subprocess
import sys

import pytest

import tinybench
from chipbench import harness


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("tinybench"))
    return tinybench.make(base), base


def test_every_piece_of_the_benchmark_is_found_by_name():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        cfg = harness.piece("configs", cell["config"])
        mix = harness.piece("mixes", cell["traffic"])
        harness.piece("limits", cell["name"])
        assert harness.runner(mix["kind"]).run
        assert cfg["name"] == cell["config"]
        assert harness.per_layer_of(spec, cell["name"])
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
    for m in spec["per_layer"]:
        assert callable(harness.reader(m["name"]))


@pytest.mark.parametrize("cell", list(tinybench.CELLS))
def test_tiny_cell_runs_correct(tiny, cell):
    spec, base = tiny
    out = tinybench.run(spec, base, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in harness.end_to_end_of(spec, cell)}
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"


def test_traced_tiny_train_cell_reports_per_layer_metrics(tiny):
    spec, base = tiny
    out = tinybench.run(spec, base, "tiny-gcn.train", trace=True)
    assert out["correct"]
    # no TPU plane on the CPU: the kernel shares have nothing to read
    assert {"pack_s", "step_mfu", "device_idle.train"} == set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_throwaway_config_mix_and_metric_are_picked_up(tiny):
    spec, base = tiny
    out = tinybench.run(spec, base, "throwaway.cell", trace=True)
    assert out["correct"]
    assert out["metrics"]["throwaway_steps"]["value"] >= 1
    out = tinybench.run(spec, base, "throwaway.cell")
    assert set(out["metrics"]) == {"setup_s", "step_s"}


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "dpgat8x8-pubmed.train", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_without_an_accelerator():
    p = _entry(harness.ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_entry_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _entry(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
