"""GNN training application (paper §6.5): GCN/GIN/GAT on a
node-classification task with ParamSpMM (or a baseline SpMM) as the
aggregation operator.  GAT aggregates through the fused
SDDMM→softmax→SpMM message function over the same PCSR.

``--partitions N`` (or ``train_gnn(partitions=N)``) swaps the
single-device operator for the distributed one (``repro.dist``): the
graph is row-partitioned over an N-device mesh and every shard runs its
own cost-model-selected ⟨W,F,V,S⟩ configuration — priced per head count
for GAT (``--heads`` works distributed: every head batches through one
head-tiled SPMD program).  ``--overlap`` turns on the halo/compute
overlap decomposition for the SpMM aggregations (see
docs/DISTRIBUTED.md).

``--mutate N`` appends a streaming-mutation demo after training: N
random insert/delete churn batches against the trained graph's
normalized adjacency through a self-healing ``repro.dynamic``
``DynamicGraph``, printing every governor verdict and verifying the
final aggregation is exact against a from-scratch re-pack (see
docs/DYNAMIC.md)."""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import make_cusparse_analog, make_gespmm_analog
from repro.compile_cache import enable_compile_cache
from repro.core.pcsr import SpMMConfig
from repro.data.tasks import NodeTask
from repro.kernels import resolve_backend
from repro.models.gnn import (accuracy, gat_forward, gcn_forward,
                              gin_forward, init_gat, init_gcn, init_gin,
                              node_ce_loss)
from repro.obs import instant, span, tracing
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.pipeline import ParamSpMM


@dataclass
class GNNTrainResult:
    losses: list = field(default_factory=list)
    val_acc: float = 0.0
    setup_seconds: float = 0.0       # host: reorder, config pick, packs
    compile_seconds: float = 0.0     # step 0: trace + compile + run
    seconds_per_step: float = 0.0    # steady steps, after block_until_ready
    config: SpMMConfig | list | None = None   # list = per-partition configs


def build_spmm(task: NodeTask, dim: int, mode: str = "paramspmm", *,
               partitions: int = 0, partition_strategy: str = "balanced",
               **kw):
    """SpMM closure over Â (GCN-normalized adjacency). Returns (fn, perm,
    config).  ``partitions > 0`` builds the distributed operator instead
    (no reorder — node ids must stay aligned with the partition map);
    config is then the per-shard list."""
    csr = task.csr.gcn_normalize()
    if partitions:
        if mode != "paramspmm":
            raise ValueError("partitioned execution needs mode='paramspmm'")
        from repro.dist import DistGraph
        g = DistGraph(csr, dim, partitions, strategy=partition_strategy, **kw)
        return g, None, g.configs
    if mode == "paramspmm":
        p = ParamSpMM(csr, dim, **kw)
        return p, p.perm, p.config
    if mode == "cusparse":
        return make_cusparse_analog(csr), None, None
    if mode == "gespmm":
        return make_gespmm_analog(csr), None, None
    raise ValueError(mode)


def train_gnn(task: NodeTask, *, model: str = "gcn", hidden: int = 64,
              n_layers: int = 5, steps: int = 100, lr: float = 5e-3,
              spmm_mode: str = "paramspmm", seed: int = 0, heads: int = 1,
              partitions: int = 0, partition_strategy: str = "balanced",
              overlap: bool = False, fused: bool = True,
              spmm_kwargs: dict | None = None) -> GNNTrainResult:
    """``fused=True`` (default) lets GCN layers hand bias + ReLU to the
    SpMM's fused epilogue (one kernel per aggregation on the Pallas
    backend); ``fused=False`` keeps the classic ``spmm(h) @ W + b`` order
    — bit-identical to the baseline backends, which never fuse."""
    kw = dict(spmm_kwargs or {})
    if partitions and overlap and model != "gat":
        # GAT's attention chain never takes the overlap path (see
        # DistGraph) — don't build the unused local/halo decomposition
        kw.setdefault("overlap", True)
    if model == "gat":
        if spmm_mode != "paramspmm":
            raise ValueError("gat needs the PCSR message fn "
                             "(spmm_mode='paramspmm')")
        # pick the config for the SDDMM+SpMM pair, not the SpMM alone —
        # priced per head count (head tiling changes the optimal F);
        # DistGraph takes the same op/heads kwargs for per-shard selection
        kw.setdefault("op", "gat")
        kw.setdefault("heads", heads)
        if not partitions:
            # engine backward is native autodiff; the Pallas backward runs
            # its dK/dVf SpMMs on the operator's cached transpose PCSR
            kw.setdefault("build_transpose",
                          resolve_backend(kw.get("backend"))[0] == "pallas")
    t_setup = time.perf_counter()
    with span("gnn.pack", model=model, mode=spmm_mode,
              partitions=partitions):
        spmm, perm, cfg = build_spmm(task, hidden, spmm_mode,
                                     partitions=partitions,
                                     partition_strategy=partition_strategy,
                                     **kw)
    if not fused and model != "gat" and hasattr(spmm, "fused"):
        op = spmm                 # hide the fusion surface: plain closure
        spmm = lambda B: op(B)    # → gcn/gin take the unfused branch
    X = jnp.asarray(task.features)
    labels = jnp.asarray(task.labels)
    tmask = jnp.asarray(task.train_mask)
    vmask = jnp.asarray(task.val_mask)
    if perm is not None:   # graph was reordered → permute node-aligned data
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        X, labels = X[jnp.asarray(inv)], labels[jnp.asarray(inv)]
        tmask, vmask = tmask[jnp.asarray(inv)], vmask[jnp.asarray(inv)]

    feat_dim = X.shape[1]
    dims = [feat_dim] + [hidden] * (n_layers - 1) + [task.n_classes]
    key = jax.random.PRNGKey(seed)
    if model == "gcn":
        params = init_gcn(key, dims)
        fwd = gcn_forward
    elif model == "gin":
        params = init_gin(key, dims)
        fwd = gin_forward
    elif model == "gat":
        import functools

        from repro.core.engine import make_gat_message_fn
        params = init_gat(key, dims, heads=heads)
        fwd = functools.partial(gat_forward, heads=heads)
        if partitions:
            # DistGraph's sharded message fn: single-head (n, d) or
            # multi-head (H, n, d) stacks, one SPMD program either way
            spmm = spmm.gat_message
        else:
            # the message fn aggregates instead of the plain-SpMM closure,
            # over the very same PCSR (+ transpose PCSR) the pipeline built
            spmm = make_gat_message_fn(spmm.op.pcsr, spmm.op.pcsr_t,
                                       backend=spmm.op.backend,
                                       interpret=spmm.op.interpret)
    else:
        raise ValueError(model)

    opt_cfg = AdamWConfig(lr=lr)
    opt = adamw_init(params)

    def loss_fn(p):
        logits = fwd(p, X, spmm)
        return node_ce_loss(logits, labels, tmask)

    grad_fn = jax.value_and_grad(loss_fn)

    res = GNNTrainResult(config=cfg,
                         setup_seconds=time.perf_counter() - t_setup)
    t0 = None
    t_step0 = time.perf_counter()
    for step in range(steps):
        # step 0 pays tracing + compilation — its span is named apart so
        # the trace separates warmup from steady-state steps
        with span("gnn.compile" if step == 0 else "gnn.step", step=step):
            loss, grads = grad_fn(params)
            params, opt = adamw_update(params, grads, opt, opt_cfg)
            if step == 0:
                jax.block_until_ready(loss)
        res.losses.append(float(loss))
        if step == 0:      # exclude jit warmup from timing
            t0 = time.perf_counter()
            res.compile_seconds = t0 - t_step0
    jax.block_until_ready(params)
    if steps > 1:
        res.seconds_per_step = (time.perf_counter() - t0) / (steps - 1)
        instant("gnn.steady_state", seconds_per_step=res.seconds_per_step)
    with span("gnn.eval"):
        logits = fwd(params, X, spmm)
        res.val_acc = float(accuracy(logits, labels, vmask))
    return res


def run_mutation_stream(csr, dim: int, batches: int, *, seed: int = 0,
                        inserts: int = 150, deletes: int = 130,
                        slack: float = 1.1, amortize_steps: int = 20):
    """Churn ``csr`` through a self-healing ``DynamicGraph`` and report
    each governor verdict; ends with an exactness check of the degraded
    aggregation against a from-scratch re-pack of the mutated edges."""
    from repro.core.engine import make_spmm_fn
    from repro.core.pcsr import build_pcsr
    from repro.dynamic import DynamicGraph

    rng = np.random.default_rng(seed)
    g = DynamicGraph(csr, dim, slack=slack, amortize_steps=amortize_steps)
    X = jnp.asarray(rng.standard_normal((csr.n_cols, dim)), jnp.float32)
    for step in range(batches):
        r, c = rng.integers(0, csr.n_rows, (2, inserts))
        g.insert_edges(r, c,
                       rng.uniform(0.5, 1.5, inserts).astype(np.float32))
        m = g.dyn.to_csr()
        rows = np.repeat(np.arange(m.n_rows), np.diff(m.indptr))
        pick = rng.permutation(m.nnz)[:deletes]
        _, dec = g.delete_edges(rows[pick], m.indices[pick])
        instant("gnn.mutate", step=step, action=dec.action)
        print(f"mutate[{step}]: nnz={g.dyn.nnz} chunks={g.dyn.num_chunks} "
              f"slot_fill={g.dyn.slot_fill:.2f} -> {dec.action} "
              f"({dec.reason})")
    out = np.asarray(g.spmm(X))
    m = g.dyn.to_csr()
    fresh = build_pcsr(m.indptr, m.indices, m.data, m.n_rows, m.n_cols,
                       g.config)
    err = float(np.abs(out - np.asarray(make_spmm_fn(fresh)(X))).max())
    n_repack = sum(d.action == "repack" for d in g.decisions)
    print(f"mutate: aggregation matches a fresh re-pack "
          f"(max |Δ| = {err:.2e}, summation-order noise only); "
          f"repacks={n_repack}")
    return g


def main(argv=None):
    from repro.data.tasks import community_task

    ap = argparse.ArgumentParser(description="GNN training on a synthetic "
                                 "node-classification task")
    ap.add_argument("--model", default="gcn", choices=["gcn", "gin", "gat"])
    ap.add_argument("--partitions", type=int, default=0,
                    help="row-partition the graph over N mesh devices "
                    "(0 = single-device)")
    ap.add_argument("--partition-strategy", default="balanced",
                    choices=["contiguous", "balanced"])
    ap.add_argument("--overlap", action="store_true",
                    help="hide the halo all_gather behind the shard-local "
                    "SpMM (DistGraph(overlap=True); needs --partitions)")
    ap.add_argument("--spmm", default="paramspmm",
                    choices=["paramspmm", "cusparse", "gespmm"])
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--heads", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mutate", type=int, default=0, metavar="N",
                    help="after training, stream N random insert/delete "
                    "churn batches through a self-healing DynamicGraph "
                    "on the trained adjacency (repro.dynamic demo)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run (read it "
                    "with repro.apps.obs_report or Perfetto)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import contextlib
    ctx = tracing(args.trace) if args.trace else contextlib.nullcontext()
    with ctx:
        task = community_task(seed=args.seed)
        res = train_gnn(task, model=args.model, hidden=args.hidden,
                        n_layers=args.layers, steps=args.steps,
                        spmm_mode=args.spmm, heads=args.heads,
                        seed=args.seed, partitions=args.partitions,
                        partition_strategy=args.partition_strategy,
                        overlap=args.overlap)
        if args.mutate:
            run_mutation_stream(task.csr.gcn_normalize(), args.hidden,
                                args.mutate, seed=args.seed)
    if args.trace:
        print(f"trace written to {args.trace}")
    print(f"val_acc={res.val_acc:.3f} "
          f"ms_per_step={res.seconds_per_step * 1e3:.1f}")
    cfgs = res.config if isinstance(res.config, list) else [res.config]
    for i, c in enumerate(cfgs):
        if c is not None:
            w, f, v, s, b = c.astuple()
            print(f"partition {i}: W={w} F={f} V={v} S={s} B={b}")


if __name__ == "__main__":
    main()
