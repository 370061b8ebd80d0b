"""The correctness check fails a run whose timed path is broken.

Each test skips the look for a chip, drives the rest of a tiny run with
the program's step broken underneath, and sees ``correct`` come out
false against the cell's own limits; the last test puts the
reference at the next precision down (``high``) in the program's place.
"""
import jax.numpy as jnp
import pytest

import tinybench
from chipbench import compare, graphs, harness
from chipbench.kinds import train

TRAIN = ["tiny-gcn.train", "tiny-dpgat.train"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("tinybench"))
    return tinybench.make(base), base


@pytest.mark.parametrize("cell", TRAIN)
def test_step_that_returns_its_state_unchanged(tiny, cell, monkeypatch):
    monkeypatch.setattr(train, "adamw_update", lambda p, g, s, c: (p, s))
    out = tinybench.run(*tiny, cell)
    assert not out["correct"]
    assert out["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN)
def test_half_the_batch_left_out(tiny, cell, monkeypatch):
    loss = train.node_ce_loss

    def half(logits, labels, mask):
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return loss(logits, labels, mask * keep)
    monkeypatch.setattr(train, "node_ce_loss", half)
    out = tinybench.run(*tiny, cell)
    assert not out["correct"]
    assert out["checks"]["grad_gap"]["value"] > \
        out["checks"]["grad_gap"]["limit"]


@pytest.mark.parametrize("cell", TRAIN)
def test_control_lower_precision_fails(tiny, cell):
    spec, base = tiny
    c = harness.find(spec["workloads"], cell, "workload")
    cfg = harness.piece("configs", c["config"], base)
    limits = harness.piece("limits", cell, base)
    u, v = graphs.graph_of(cfg)
    best = train.reference_readings(cfg, 11, u, v, 3, "highest")
    high = train.reference_readings(cfg, 11, u, v, 3, "high")
    checks = compare.train_checks(high, best, limits)
    assert not harness.passed(checks), checks

