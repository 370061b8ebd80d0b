"""The one-way layering of the GNN system, and the one GAT pipeline.

``core.pcsr`` (format) → ``kernels/*`` (each kernel and its one dispatch)
→ ``core.engine`` (the operators) → ``dist``, ``serve``, ``dynamic``,
``apps`` (adapters).  A module above the kernels reaches a kernel only
through its dispatch, and the engine's private traversal helpers stay in
``core.engine``; the GAT message — written once in ``core.engine`` — is
the same computation on one device, on a ``DistGraph`` and in a serving
bucket.
"""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
KERNEL_BODIES = {"paramspmm_kernel", "sddmm_kernel", "sddmm_softmax_kernel"}
ENGINE_PRIVATE = {"_engine", "_engine_sddmm", "_slot_rows"}
MODULES = sorted(str(p.relative_to(SRC)) for p in SRC.rglob("*.py")
                 if p.relative_to(SRC).parts[0] != "kernels")


def _imports(path: pathlib.Path):
    """``(module, name)`` of every ``from … import`` in ``path``, relative
    imports resolved against its package (``name`` is None for a plain
    ``import``)."""
    pkg = ["repro"] + list(path.relative_to(SRC).parent.parts)
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            for a in node.names:
                yield mod, a.name
        elif isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None


@pytest.mark.parametrize("rel", MODULES)
def test_module_reaches_kernels_only_through_their_dispatch(rel):
    path = SRC / rel
    bad = []
    for mod, name in _imports(path):
        full = f"{mod}.{name}" if name else mod
        if full.startswith("repro.kernels.") and (
                full.split(".")[3:4] == ["kernel"] or name in KERNEL_BODIES):
            bad.append(full)                      # a kernel body
        if (mod.startswith("repro.kernels.") and mod.endswith(".ops")
                and name and name.startswith("_")):
            bad.append(full)                      # a dispatch's private
        if (mod == "repro.core.engine" and name in ENGINE_PRIVATE
                and rel != "core/engine.py"):
            bad.append(full)                      # the engine's private
    assert not bad, f"{rel} imports {bad}"


# ------------------------------------------- one GAT pipeline, three paths
@pytest.mark.parametrize("backend", ["engine", "pallas"])
def test_gat_same_on_one_device_dist_and_serve(backend):
    """One graph, one config: the GAT model's output and its parameter
    gradients agree when the message is the single-device operator, a
    one-partition ``DistGraph`` and a serving bucket's forward.  Serving
    runs inference only: its Pallas forward has no VJP, so its gradients
    are compared on the engine."""
    from repro.core.engine import make_gat_message_fn
    from repro.core.pcsr import SpMMConfig, build_pcsr
    from repro.data.graphs import er
    from repro.dist import DistGraph, dist_gat_message
    from repro.models.gnn import gat_forward, init_gat
    from repro.serve import PackGeom, ShapeBucket, pack_subgraph
    from repro.serve.bucket import steering_arrays
    from repro.serve.forward import bucket_forward

    csr = er(60, 5, seed=3)
    n, cfg = csr.n_rows, SpMMConfig(V=2, S=True, W=8)
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    params = init_gat(jax.random.PRNGKey(0), [8, 12, 4])

    p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n, cfg)
    one = make_gat_message_fn(p, backend=backend, interpret=True)
    g = DistGraph(csr, 12, 1, configs=cfg, op="gat", backend=backend,
                  interpret=True)
    geom = PackGeom.from_bucket(ShapeBucket(64, 512), cfg)
    steer = steering_arrays(pack_subgraph(csr, geom))
    X_pad = jnp.zeros((geom.n_rows, X.shape[1]), X.dtype).at[:n].set(X)

    runs = {
        "one": lambda ps: gat_forward(ps, X, one),
        "dist": lambda ps: gat_forward(
            ps, X, lambda q, k, v: dist_gat_message(g, q, k, v)),
        "serve": lambda ps: bucket_forward(
            steer, X_pad, ps, geom=geom, model="gat", backend=backend,
            interpret=True)[:n],
    }
    outs = {k: np.asarray(f(params)) for k, f in runs.items()}
    for k in ("dist", "serve"):
        np.testing.assert_allclose(outs[k], outs["one"], rtol=1e-5,
                                   atol=1e-5, err_msg=k)
    grads = {k: jax.grad(lambda ps: (f(ps) ** 2).sum())(params)
             for k, f in runs.items()
             if not (k == "serve" and backend == "pallas")}
    for k in grads.keys() - {"one"}:
        for a, b in zip(jax.tree.leaves(grads[k]),
                        jax.tree.leaves(grads["one"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5, err_msg=k)
