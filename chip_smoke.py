#!/usr/bin/env python3
"""Bring-up smoke run of the GNN system on a TPU, in one process.

Drives the normal entry points once on the smoke graph — a 65,536-node
community graph (``community_task(n_blocks=16, block_size=4096,
p_in=0.005)``: 1,403,706 nonzeros, 16 classes) — with random weights
from ``--seed``:

* **gcn** — ``configs/gcn.py`` CONFIG (5 layers, 16 → 64 → 16) trained
  through ``repro.apps.gnn.train_gnn``;
* **gat** — ``configs/gat.py`` GAT_MH (3 layers, hidden 64, 4 heads)
  trained through the same entry point;
* **serve** — GCN requests through ``repro.serve.GNNService``.

Each phase checks itself against a plain float32 segment-sum reference
that never touches the kernels, computed under
``jax.default_matmul_precision("highest")`` (the phases run under it
too, so only summation order separates the two): training phases
compare the first step's loss and gradients and require the loss to
fall; the serving phase compares every served output.  It prints the
picked ⟨W,F,V,S,B⟩, host set-up seconds, step-0 (compile) seconds,
seconds per steady step after ``block_until_ready`` and the max |Δ|.

    python chip_smoke.py             # one chip: gcn, gat, serve
    python chip_smoke.py --chips 4   # GCN over 4 DistGraph partitions
                                     # vs the single-device GCN

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails or any phase raises, the script
exits non-zero and never prints it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SMOKE_GRAPH = dict(n_blocks=16, block_size=4096, p_in=0.005)
LOSS_RTOL = 1e-4         # f32 summation-order noise on a mean loss
GRAD_RTOL = 1e-3         # per leaf, relative to the leaf's largest entry
OUT_RTOL = 1e-4          # served outputs, relative to the largest output


class SmokeCheckFailed(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeCheckFailed(msg)


def highest():
    import jax
    return jax.default_matmul_precision("highest")


def smoke_task(seed: int = 0, **graph):
    from repro.data.tasks import community_task
    return community_task(seed=seed, **(graph or SMOKE_GRAPH))


# ------------------------------------------------------------ references
def segment_spmm(csr):
    """Plain ``A·B`` by gather + segment-sum over ``csr``'s edges."""
    from repro.kernels.paramspmm.ref import spmm_ref
    return lambda B: spmm_ref(csr.indptr, csr.indices, csr.data, B,
                              csr.n_rows)


def segment_gat_message(csr, slope: float = 0.2, chunk: int = 1 << 16):
    """Plain GAT message over ``csr``'s edge pattern: per destination row
    softmax_j(LeakyReLU(q_i·k_j/√d)), then Σ_j α_ij·v_j; single-head
    ``(n, d)`` or multi-head ``(H, n, d)`` operands.

    The gathered ``(E, d)`` rows are made ``chunk`` edges at a time under
    ``jax.checkpoint``, so the backward pass recomputes them instead of
    keeping them: a narrow head's gathered row pads to 128 lanes on a
    TPU, and kept whole they put the 4-head smoke reference at 36 GB.
    Per-edge scalars stay whole; padding edges score −inf and weigh 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, nnz = csr.n_rows, csr.nnz
    n_chunks = -(-nnz // chunk)
    pad = n_chunks * chunk - nnz
    rows_np = np.repeat(np.arange(n), np.diff(csr.indptr))
    rows = jnp.asarray(np.pad(rows_np, (0, pad)), jnp.int32)
    cols = jnp.asarray(np.pad(csr.indices, (0, pad)), jnp.int32)
    real = jnp.arange(n_chunks * chunk) < nnz
    by_chunk = lambda x: x.reshape(n_chunks, chunk)

    def one(q, k, v):
        @jax.checkpoint
        def scores(rc):
            r, c = rc
            return jnp.sum(q[r] * k[c], axis=1)

        @jax.checkpoint
        def messages(rcw):
            r, c, w = rcw
            return jax.ops.segment_sum(w[:, None] * v[c], r, num_segments=n)

        s = jax.lax.map(scores, (by_chunk(rows), by_chunk(cols))).reshape(-1)
        s = s / jnp.sqrt(jnp.asarray(q.shape[1], q.dtype))
        s = jnp.where(real, jax.nn.leaky_relu(s, negative_slope=slope),
                      -jnp.inf)
        m = jax.ops.segment_max(s, rows, num_segments=n)
        e = jnp.exp(s - m[rows])
        den = jax.ops.segment_sum(e, rows, num_segments=n)
        w = e / den[rows]
        return jax.lax.map(messages, (by_chunk(rows), by_chunk(cols),
                                      by_chunk(w))).sum(0)

    def msg(Q, K, Vf):
        if Q.ndim == 2:
            return one(Q, K, Vf)
        return jnp.stack([one(*qkv) for qkv in zip(Q, K, Vf)])
    return msg


# --------------------------------------------------------------- helpers
def model_dims(task, hidden: int, n_layers: int) -> list:
    """Layer widths exactly as ``train_gnn`` builds them."""
    return ([task.features.shape[1]] + [hidden] * (n_layers - 1)
            + [task.n_classes])


def max_rel_diff(a, b) -> float:
    """max |a − b| over max |b|, over every leaf of two pytrees."""
    import jax
    import numpy as np
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        worst = max(worst, float(np.abs(x - y).max())
                    / max(float(np.abs(y).max()), 1e-30))
    return worst


def permuted(task, perm):
    """Node-aligned task arrays in the operator's (reordered) node order,
    as ``train_gnn`` lays them out."""
    import jax.numpy as jnp
    import numpy as np
    arrs = [task.features, task.labels, task.train_mask]
    if perm is not None:
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        arrs = [a[inv] for a in arrs]
    return [jnp.asarray(a) for a in arrs]


def fmt_config(cfg) -> str:
    w, f, v, s, b = cfg.astuple()
    return f"<W={w},F={f},V={v},S={s},B={b}>"


def check_training(name, res, loss_k, grads_k, loss_r, grads_r) -> dict:
    """The two training checks: first step against the reference, and
    the loss falling over the steps."""
    import numpy as np
    d_loss = abs(float(loss_k) - float(loss_r))
    d_train = abs(res.losses[0] - float(loss_r))
    d_grad = max_rel_diff(grads_k, grads_r)
    log(f"{name}: config {fmt_config(res.config)} setup "
        f"{res.setup_seconds:.2f}s compile(step 0) {res.compile_seconds:.2f}s"
        f" {res.seconds_per_step:.4f}s/step")
    log(f"{name}: losses {[round(x, 6) for x in res.losses]}")
    log(f"{name}: first step vs reference: |dloss| {d_loss:.3e} "
        f"(train_gnn step 0: {d_train:.3e}), grads max|d|/max|g| "
        f"{d_grad:.3e}")
    tol = LOSS_RTOL * max(1.0, abs(float(loss_r)))
    require(np.isfinite(res.losses).all(), f"{name}: non-finite loss")
    require(d_loss <= tol and d_train <= tol,
            f"{name}: first-step loss off the reference by "
            f"{max(d_loss, d_train):.3e} (> {tol:.1e})")
    require(d_grad <= GRAD_RTOL,
            f"{name}: gradients off the reference by {d_grad:.3e} "
            f"(> {GRAD_RTOL:.0e})")
    require(res.losses[-1] < res.losses[0],
            f"{name}: loss did not fall ({res.losses[0]} -> "
            f"{res.losses[-1]})")
    return {"config": res.config.astuple(), "losses": res.losses,
            "setup_s": res.setup_seconds, "compile_s": res.compile_seconds,
            "s_per_step": res.seconds_per_step, "dloss": d_loss,
            "dgrad_rel": d_grad}


def require_kernels(hlo: str, interpret: bool, what: str) -> None:
    """A compiled program on the kernel path holds Mosaic custom calls."""
    if not interpret:
        require("tpu_custom_call" in hlo,
                f"{what}: no tpu_custom_call in the compiled HLO")


def require_launches(launched: list, interpret: bool, what: str) -> None:
    """Pallas kernels were traced (``intercept_pallas``) on the compiled
    path, for entry points that jit their own programs."""
    if not interpret:
        require(bool(launched), f"{what}: no Pallas kernel was launched")


# ---------------------------------------------------------------- phases
def phase_gcn(task, *, hidden: int = 64, n_layers: int = 5, steps: int = 6,
              seed: int = 0, spmm_kwargs: dict | None = None) -> dict:
    """GCN through ``train_gnn``, plus the first step as one jitted
    value-and-grad program on the same kernel path, both against the
    segment-sum reference."""
    import jax
    from repro.apps.gnn import build_spmm, train_gnn
    from repro.models.gnn import gcn_forward, init_gcn, node_ce_loss

    kw = dict(spmm_kwargs or {})
    params0 = init_gcn(jax.random.PRNGKey(seed),
                       model_dims(task, hidden, n_layers))
    with highest():
        res = train_gnn(task, model="gcn", hidden=hidden, n_layers=n_layers,
                        steps=steps, seed=seed, spmm_kwargs=kw)
        op, perm, _ = build_spmm(task, hidden, **kw)
        X, y, m = permuted(task, perm)
        step = jax.jit(jax.value_and_grad(
            lambda p: node_ce_loss(gcn_forward(p, X, op), y, m)))
        compiled = step.lower(params0).compile()
        require_kernels(compiled.as_text(), op.op.interpret, "gcn step")
        loss_k, grads_k = compiled(params0)
        Xr, yr, mr = permuted(task, None)
        ref = segment_spmm(task.csr.gcn_normalize())
        loss_r, grads_r = jax.jit(jax.value_and_grad(
            lambda p: node_ce_loss(gcn_forward(p, Xr, ref), yr, mr)))(params0)
    return check_training("gcn", res, loss_k, grads_k, loss_r, grads_r)


def phase_gat(task, *, hidden: int = 64, n_layers: int = 3, heads: int = 4,
              steps: int = 4, seed: int = 0,
              spmm_kwargs: dict | None = None) -> dict:
    """Multi-head GAT through ``train_gnn``; the first step's loss and
    gradients again on the kernel path (op by op, as ``train_gnn`` runs
    it) against the segment-sum reference."""
    import jax
    from repro.apps.gnn import build_spmm, train_gnn
    from repro.core.engine import make_gat_message_fn
    from repro.models.gnn import gat_forward, init_gat, node_ce_loss
    from repro.obs.metrics import intercept_pallas

    kw = dict(spmm_kwargs or {})
    params0 = init_gat(jax.random.PRNGKey(seed),
                       model_dims(task, hidden, n_layers), heads=heads)
    launched = []
    with highest():
        with intercept_pallas(launched.append):
            res = train_gnn(task, model="gat", hidden=hidden,
                            n_layers=n_layers, heads=heads, steps=steps,
                            seed=seed, spmm_kwargs=kw)
        op, perm, _ = build_spmm(task, hidden, op="gat", heads=heads,
                                 build_transpose=True, **kw)
        require_launches(launched, op.op.interpret, "gat train_gnn")
        msg = make_gat_message_fn(op.op.pcsr, op.op.pcsr_t,
                                  backend=op.op.backend,
                                  interpret=op.op.interpret)
        X, y, m = permuted(task, perm)
        loss_k, grads_k = jax.value_and_grad(
            lambda p: node_ce_loss(gat_forward(p, X, msg, heads=heads),
                                   y, m))(params0)
        Xr, yr, mr = permuted(task, None)
        ref = segment_gat_message(task.csr.gcn_normalize())
        loss_r, grads_r = jax.jit(jax.value_and_grad(
            lambda p: node_ce_loss(gat_forward(p, Xr, ref, heads=heads),
                                   yr, mr)))(params0)
    return check_training("gat", res, loss_k, grads_k, loss_r, grads_r)


def phase_serve(task, *, hidden: int = 64, n_layers: int = 5,
                requests: int = 12, tick_every: int = 4, seed: int = 0,
                spmm_kwargs: dict | None = None) -> dict:
    """GCN requests through ``GNNService``: every served output against
    the full-pipeline reference on its sampled subgraph (as
    ``serve_gnn --check`` does) and against the segment-sum reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.gnn import gcn_forward, init_gcn
    from repro.obs.metrics import intercept_pallas
    from repro.serve import (GNNService, reference_forward, replay,
                             synthetic_stream)

    kw = dict(spmm_kwargs or {})
    csr = task.csr.gcn_normalize()
    feats = np.asarray(task.features, np.float32)
    params = init_gcn(jax.random.PRNGKey(seed),
                      model_dims(task, hidden, n_layers))
    launched = []
    with highest():
        svc = GNNService(csr, feats, params, model="gcn",
                         keep_subgraphs=True, **kw)
        t0 = time.perf_counter()
        with intercept_pallas(launched.append):
            results = replay(svc, synthetic_stream(requests, csr.n_rows,
                                                   seed=seed),
                             tick_every=tick_every)
        wall = time.perf_counter() - t0
        require_launches(launched, svc.interpret, "serve GNNService")
        d_pipe = d_ref = 0.0
        for r in results:
            sr = r.sampled
            Xs = feats[sr.nodes]
            pipe = np.asarray(reference_forward(
                sr.sub, Xs, params, model="gcn", config=r.config,
                backend=svc.backend, interpret=svc.interpret))[sr.seed_local]
            ref = np.asarray(gcn_forward(params, jnp.asarray(Xs),
                                         segment_spmm(sr.sub)))[sr.seed_local]
            scale = max(float(np.abs(ref).max()), 1e-30)
            d_pipe = max(d_pipe, float(np.abs(r.outputs - pipe).max()) / scale)
            d_ref = max(d_ref, float(np.abs(r.outputs - ref).max()) / scale)
    lat = np.array([r.latency_s for r in results])
    log(f"serve: {len(results)} requests in {len(svc.batch_log)} batches, "
        f"{svc.compiled_buckets} compiled buckets, configs "
        f"{sorted({fmt_config(r.config) for r in results})}, "
        f"{wall:.2f}s wall, latency p50 {np.percentile(lat, 50):.4f}s")
    log(f"serve: served vs full-pipeline max|d|/max|y| {d_pipe:.3e}, "
        f"vs segment-sum reference {d_ref:.3e}")
    require(len(results) == requests, "serve: requests went unanswered")
    require(all(np.isfinite(r.outputs).all() for r in results),
            "serve: non-finite outputs")
    require(d_pipe <= OUT_RTOL and d_ref <= OUT_RTOL,
            f"serve: outputs off the reference by {max(d_pipe, d_ref):.3e} "
            f"(> {OUT_RTOL:.0e})")
    return {"requests": len(results), "batches": len(svc.batch_log),
            "wall_s": wall, "d_pipeline": d_pipe, "d_reference": d_ref}


def phase_dist(task, *, parts: int = 4, hidden: int = 64, n_layers: int = 5,
               steps: int = 4, seed: int = 0,
               spmm_kwargs: dict | None = None) -> dict:
    """GCN over ``parts`` DistGraph partitions against the single-device
    GCN on the same graph, after checking that the partition mesh
    really spans ``parts`` devices and the SPMD program holds kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.gnn import build_spmm, train_gnn

    kw = dict(spmm_kwargs or {})
    g, _, _ = build_spmm(task, hidden, partitions=parts, **kw)
    mesh_devs = {d.id for d in g.mesh.devices.flat}
    require(len(mesh_devs) == parts,
            f"dist: partition mesh spans {sorted(mesh_devs)}, not {parts} "
            f"devices")
    B = jnp.ones((task.csr.n_rows, hidden), jnp.float32)
    compiled = jax.jit(lambda b: g.fused(b, activation="relu")).lower(
        B).compile()
    hlo = compiled.as_text()
    require_kernels(hlo, g.interpret, "dist fused SpMM")
    ran_on = {d.id for d in compiled.runtime_executable().local_devices()}
    require(ran_on == mesh_devs and f"num_partitions={parts}" in hlo,
            f"dist: the SPMD program runs on devices {sorted(ran_on)}, "
            f"not one partition per mesh device")
    require(bool(np.isfinite(np.asarray(compiled(B))).all()),
            "dist: non-finite aggregation")
    with highest():
        one = train_gnn(task, model="gcn", hidden=hidden, n_layers=n_layers,
                        steps=steps, seed=seed, spmm_kwargs=kw)
        dist = train_gnn(task, model="gcn", hidden=hidden,
                         n_layers=n_layers, steps=steps, seed=seed,
                         partitions=parts, spmm_kwargs=kw)
    d = max(abs(a - b) / max(abs(b), 1e-30)
            for a, b in zip(dist.losses, one.losses))
    log(f"dist: mesh devices {sorted(mesh_devs)}; per-partition configs "
        f"{[fmt_config(c) for c in dist.config]}")
    log(f"dist: single-device losses {[round(x, 6) for x in one.losses]}")
    log(f"dist: {parts}-partition losses {[round(x, 6) for x in dist.losses]}")
    log(f"dist: setup {dist.setup_seconds:.2f}s compile(step 0) "
        f"{dist.compile_seconds:.2f}s {dist.seconds_per_step:.4f}s/step "
        f"(single device {one.seconds_per_step:.4f}s/step); max rel |dloss| "
        f"{d:.3e}")
    require(np.isfinite(dist.losses).all(), "dist: non-finite loss")
    require(d <= LOSS_RTOL, f"dist: losses differ from the single-device "
            f"run by {d:.3e} (> {LOSS_RTOL:.0e})")
    return {"losses": dist.losses, "single_losses": one.losses,
            "dloss_rel": d, "s_per_step": dist.seconds_per_step}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the GCN over four DistGraph partitions "
                    "and the single-device GCN it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import resolve_backend
    log(f"chip_smoke: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {enable_compile_cache()}; backend {resolve_backend()}")
    require(resolve_backend() == ("pallas", False),
            "the TPU default is not the compiled kernel path")
    t0 = time.perf_counter()
    task = smoke_task(args.seed)
    log(f"graph: {task.csr.n_rows} nodes, {task.csr.nnz} nonzeros, "
        f"{task.n_classes} classes, generated in "
        f"{time.perf_counter() - t0:.2f}s")
    if args.chips == 4:
        phases = [("dist", phase_dist)]
    else:
        phases = [("gcn", phase_gcn), ("gat", phase_gat),
                  ("serve", phase_serve)]
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(task, seed=args.seed)
        log(f"{name}: phase passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
