"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases — at a tiny size, kernels in interpret mode — pass their own
reference checks."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

from repro.kernels import resolve_backend

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(n_blocks=4, block_size=48, p_in=0.2)
PALLAS_INTERPRET = {"backend": "pallas", "interpret": True}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def task(smoke):
    return smoke.smoke_task(0, **TINY)


def test_resolver_picks_engine_and_interpret_on_cpu():
    assert resolve_backend() == ("engine", True)
    assert resolve_backend("pallas") == ("pallas", True)
    assert resolve_backend("pallas", False) == ("pallas", False)
    with pytest.raises(ValueError):
        resolve_backend("cuda")


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_phase_gcn_tiny(smoke, task):
    r = smoke.phase_gcn(task, hidden=16, n_layers=3, steps=3,
                        spmm_kwargs=PALLAS_INTERPRET)
    assert r["dloss"] <= smoke.LOSS_RTOL and r["dgrad_rel"] <= smoke.GRAD_RTOL
    assert r["losses"][-1] < r["losses"][0]


def test_phase_gat_tiny(smoke, task):
    r = smoke.phase_gat(task, hidden=16, n_layers=2, heads=2, steps=3,
                        spmm_kwargs=PALLAS_INTERPRET)
    assert r["dgrad_rel"] <= smoke.GRAD_RTOL
    assert r["losses"][-1] < r["losses"][0]


def test_phase_serve_tiny(smoke, task):
    r = smoke.phase_serve(task, hidden=16, n_layers=2, requests=4,
                          tick_every=2, spmm_kwargs=PALLAS_INTERPRET)
    assert r["requests"] == 4
    assert max(r["d_pipeline"], r["d_reference"]) <= smoke.OUT_RTOL


def test_check_training_rejects_a_wrong_gradient(smoke, task):
    """The reference check is live: a perturbed gradient fails it."""
    import jax
    from repro.apps.gnn import GNNTrainResult
    from repro.core.pcsr import SpMMConfig
    res = GNNTrainResult(losses=[1.0, 0.5], config=SpMMConfig())
    g = {"w": jax.numpy.ones(3)}
    with pytest.raises(smoke.SmokeCheckFailed):
        smoke.check_training("t", res, 1.0, {"w": g["w"] * 1.01}, 1.0, g)
    with pytest.raises(smoke.SmokeCheckFailed):
        smoke.check_training("t", GNNTrainResult(losses=[1.0, 1.5],
                                                 config=SpMMConfig()),
                             1.0, g, 1.0, g)
    json.dumps(smoke.check_training("t", res, 1.0, g, 1.0, g),
               default=float)


def test_kernel_checks_reject_a_missing_kernel(smoke):
    """On the compiled path a program without kernels fails the checks;
    in interpret mode they do not apply."""
    with pytest.raises(smoke.SmokeCheckFailed):
        smoke.require_kernels("HloModule m", False, "t")
    with pytest.raises(smoke.SmokeCheckFailed):
        smoke.require_launches([], False, "t")
    smoke.require_kernels("HloModule m", True, "t")
    smoke.require_launches([], True, "t")
    smoke.require_launches(["paramspmm_v2"], False, "t")


def test_phase_dist_tiny(smoke, task):
    """The 4-partition GCN against the single-device GCN; needs four
    (virtual) devices: XLA_FLAGS=--xla_force_host_platform_device_count=4."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    r = smoke.phase_dist(task, hidden=16, n_layers=3, steps=3,
                         spmm_kwargs=PALLAS_INTERPRET)
    assert r["dloss_rel"] <= smoke.LOSS_RTOL
