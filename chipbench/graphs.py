"""The benchmark's graph and node data, generated from seeds.

The graph is the configuration's dataset: an R-MAT graph (Graph500
parameters a=0.57, b=0.19, c=0.19) with exactly the configuration's node
count and undirected edge count, no self loops, no duplicate edges, node
ids shuffled.  It is made from the configuration's fixed ``graph_seed``,
so every run of a cell packs and compiles the same graph; ``--seed``
draws the features, labels, training nodes and weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.work import model_dims


def rmat_edges(n: int, m: int, seed: int, a: float = 0.57, b: float = 0.19,
               c: float = 0.19) -> tuple[np.ndarray, np.ndarray]:
    """``m`` distinct undirected edges ``(u, v)``, ``u < v < n``, drawn by
    R-MAT over ``2**ceil(log2 n)`` ids; draws landing on an id ``>= n``,
    a self loop or a repeated edge are dropped, and the first ``m``
    distinct edges in draw order are kept.  Ids are then shuffled."""
    rng = np.random.default_rng(seed)
    scale = max(1, int(np.ceil(np.log2(n))))
    keys = np.zeros(0, np.int64)
    while True:
        batch = int(2.2 * (m - keys.size)) + 4096
        src = np.zeros(batch, np.int64)
        dst = np.zeros(batch, np.int64)
        for _ in range(scale):
            r = rng.random(batch)
            src = 2 * src + (r >= a + b)
            dst = 2 * dst + (((r >= a) & (r < a + b)) | (r >= a + b + c))
        ok = (src < n) & (dst < n) & (src != dst)
        lo, hi = np.minimum(src[ok], dst[ok]), np.maximum(src[ok], dst[ok])
        keys = np.concatenate([keys, lo * n + hi])
        _, first = np.unique(keys, return_index=True)
        if first.size >= m:
            keys = keys[np.sort(first)[:m]]
            break
        keys = keys[np.sort(first)]
    perm = rng.permutation(n)
    return perm[keys // n], perm[keys % n]


def graph_of(cfg: dict):
    """``(u, v)`` edge arrays of the configuration's graph."""
    g = cfg["graph"]
    return rmat_edges(g["nodes"], g["edges"], g["graph_seed"],
                      *g.get("rmat_abc", (0.57, 0.19, 0.19)))


def seed_key(seed: int):
    """A JAX PRNG key from any non-negative whole number (past 32 bits
    too): the low word seeds the key, the high word is folded in."""
    if seed < 0:
        raise ValueError("--seed must be a non-negative whole number")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(key, model: dict):
    """Weights in the program's pytree layout: N(0, 2/fan_in) matrices,
    zero biases; GAT hidden layers split their width over the heads."""
    dims = model_dims(model)
    params = []
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        key, *ks = jax.random.split(key, 4)
        w = lambda k, d: (jax.random.normal(k, (d_in, d), jnp.float32)
                          * jnp.sqrt(2.0 / d_in))
        if model["kind"] == "gcn":
            params.append({"w": w(ks[0], d_out),
                           "b": jnp.zeros(d_out, jnp.float32)})
        else:
            H = model["heads"]
            last = i == len(dims) - 2
            width = H * (d_out if last else d_out // H)
            params.append({"wq": w(ks[0], width), "wk": w(ks[1], width),
                           "wv": w(ks[2], width),
                           "b": jnp.zeros(d_out, jnp.float32)})
    return params


@functools.partial(jax.jit, static_argnames=("model", "n", "n_train"))
def _inputs(key, *, model, n, n_train):
    model = dict(model)
    kx, ky, km, kw = jax.random.split(key, 4)
    X = jax.random.normal(kx, (n, model["in_features"]), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, model["classes"], jnp.int32)
    mask = jnp.zeros(n, jnp.float32).at[
        jax.random.permutation(km, n)[:n_train]].set(1.0)
    return X, y, mask, init_params(kw, model)


def inputs(cfg: dict, seed: int):
    """Features, labels, training mask and weights from ``seed``, made
    on the device in one jitted call, in the graph's own node order."""
    return _inputs(seed_key(seed), model=tuple(sorted(cfg["model"].items())),
                   n=cfg["graph"]["nodes"],
                   n_train=cfg["graph"]["train_nodes"])
