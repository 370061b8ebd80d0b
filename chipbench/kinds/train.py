"""Full-graph training: the runner of every mix of kind ``train``.

Set-up generates the configuration's graph, builds the program's
aggregation operator for it (``repro.apps.gnn.build_spmm``, as
``train_gnn`` calls it), makes the features, labels, training nodes and
weights from ``--seed`` on the device, and compiles one ``jax.jit``
program for the whole step: ``value_and_grad`` of ``node_ce_loss`` over
the program's forward, then ``adamw_update``.  That one compiled step
runs the first ``mix["checked_steps"]`` steps in set-up (the first one
compiles), and the window goes on calling it until ``--seconds`` have
passed, each step ending in ``block_until_ready`` on the loss, the
parameters and the optimizer state.

After the window the reference (``chipbench.reference``) redoes the
first steps from the same seed and the run compares each step's loss,
each leaf's first gradient (read back from the optimizer's first moment
after step 1) and each leaf's parameter change over the checked steps.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, harness, reference, work
from chipbench import trace as tracing
from chipbench.graphs import graph_of, inputs
from chipbench.peaks import peaks_of
from repro.apps.gnn import build_spmm
from repro.core.engine import make_gat_message_fn
from repro.core.sparse import CSRMatrix
from repro.models.gnn import gat_forward, gcn_forward, node_ce_loss
from repro.optim import AdamWConfig, adamw_init, adamw_update


def aggregation(model: dict, csr, **kw):
    """The program's aggregation over ``csr`` as ``train_gnn`` builds it:
    ``(agg, forward, perm)``."""
    task = SimpleNamespace(csr=csr)
    if model["kind"] == "gcn":
        op, perm, _ = build_spmm(task, model["hidden"], **kw)
        return op, gcn_forward, perm
    H = model["heads"]
    op, perm, _ = build_spmm(task, model["hidden"], op="gat", heads=H,
                             build_transpose=True, **kw)
    msg = make_gat_message_fn(op.op.pcsr, op.op.pcsr_t,
                              backend=op.op.backend,
                              interpret=op.op.interpret)
    return msg, functools.partial(gat_forward, heads=H), perm


def make_step(fwd, agg, opt: AdamWConfig):
    def train_step(params, state, X, y, mask):
        loss, grads = jax.value_and_grad(
            lambda p: node_ce_loss(fwd(p, X, agg), y, mask))(params)
        params, state = adamw_update(params, grads, state, opt)
        return loss, params, state
    return jax.jit(train_step)


def run(cfg: dict, mix: dict, limits: dict, *, seed: int, seconds: float,
        trace: bool, devs, t0: float, out_dir: str, backend=None,
        interpret=None) -> dict:
    model, o = cfg["model"], cfg["optimizer"]
    n = cfg["graph"]["nodes"]
    opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    checked = mix["checked_steps"]
    precision = cfg["precision"]["matmul"]
    with jax.default_matmul_precision(precision):
        u, v = graph_of(cfg)
        csr = CSRMatrix.from_edges(u, v, n, symmetrize=True)
        t = time.perf_counter()
        agg, fwd, perm = aggregation(model, csr, backend=backend,
                                     interpret=interpret)
        pack_s = time.perf_counter() - t
        harness.log(f"train: graph {n} nodes, {csr.nnz} edges; pack "
                    f"{pack_s:.3f}s")
        X, y, mask, params0 = inputs(cfg, seed)
        if perm is not None:       # the operator's node order
            inv = np.empty_like(perm)
            inv[perm] = np.arange(perm.size)
            X, y, mask = (a[jnp.asarray(inv)] for a in (X, y, mask))
        step = make_step(fwd, agg, opt)
        state = adamw_init(params0)
        params, losses = params0, []
        for i in range(checked):
            loss, params, state = step(params, state, X, y, mask)
            losses.append(float(loss))
            if i == 0:    # the first gradient, as the optimizer got it
                grad_norms = [g / (1.0 - o["b1"])
                              for g in reference.leaf_norms(state["m"])]
                delta1 = reference.change_norms(params, params0)
                t_first = time.perf_counter() - t
        prog = {"losses": losses, "grad_norms": grad_norms,
                "delta1_norms": delta1,
                "delta_norms": reference.change_norms(params, params0)}
        del params0
        setup_s = time.perf_counter() - t0
        harness.log(f"train: set-up {setup_s:.3f}s (to the end of step 1 "
                    f"after the pack: {t_first:.3f}s); losses {losses}")

        if trace:
            shutil.rmtree(out_dir, ignore_errors=True)
        steps, bad = 0, 0
        with (tracing.capture(out_dir) if trace
              else contextlib.nullcontext()) as found:
            w0 = time.perf_counter()
            while True:
                with jax.profiler.StepTraceAnnotation("step",
                                                      step_num=steps):
                    loss, params, state = step(params, state, X, y, mask)
                    jax.block_until_ready((loss, params, state))
                steps += 1
                bad += not np.isfinite(float(loss))
                if time.perf_counter() - w0 >= seconds:
                    break
            window = time.perf_counter() - w0
        step_s = window / steps
        peak = harness.memory_peak_bytes(devs)
        harness.log(f"train: {steps} steps in {window:.3f}s: step_s "
                    f"{step_s:.6f}; last loss {float(loss)}; peak "
                    f"{peak} bytes")
        del agg, step, params, state, X, y, mask, loss
        gc.collect()
        jax.clear_caches()

    red = bd = None
    if trace:
        red = tracing.reduce_events(tracing.events(found[0]))
        bd = tracing.breakdown(red)
    t = time.perf_counter()
    ref = reference_readings(cfg, seed, u, v, checked)
    harness.log(f"train: reference {time.perf_counter() - t:.3f}s; "
                f"losses {ref['losses']}")
    checks = compare.train_checks(prog, ref, limits)
    peaks = peaks_of(devs[0].device_kind)
    nnz = csr.nnz + n
    return {
        "e2e": {"setup_s": setup_s, "step_s": step_s},
        "checks": checks, "readings": {"prog": prog, "ref": ref},
        "attempted": steps, "failed": bad,
        "memory_peak_bytes": peak, "trace": red, "breakdown": bd,
        "ctx": {"pack_s": pack_s, "step_s": step_s, "steps": steps,
                "trace": red, "peaks": peaks,
                "work": work.step_work(model, n, nnz)},
    }


def reference_readings(cfg: dict, seed: int, u, v, steps: int,
                       precision: str = "highest", mask_fn=None) -> dict:
    """The reference's readings over the first ``steps`` steps from the
    seed's inputs (``mask_fn`` may change the training mask)."""
    with jax.default_matmul_precision("highest"):
        adj = reference.gcn_adjacency(u, v, cfg["graph"]["nodes"])
        X, y, mask, params = inputs(cfg, seed)
        if mask_fn is not None:
            mask = mask_fn(mask)
        return reference.train_readings(cfg["model"], cfg["optimizer"], adj,
                                        params, X, y, mask, precision, steps)
