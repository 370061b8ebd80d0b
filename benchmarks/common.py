"""Shared benchmark infrastructure: cached corpus/features, timing, CSV."""
from __future__ import annotations

import functools
import sys
import time

import jax
import numpy as np

from repro.core.autotune import throughput_gflops, time_fn
from repro.core.features import extract_features
from repro.data.graphs import corpus

ROWS: list[tuple] = []


def emit(name: str, us_per_call: float | None, derived: str = ""):
    """``us_per_call=None`` marks a row with no timing (a skipped
    measurement — the derived field must say why via ``skipped=...``)."""
    ROWS.append((name, us_per_call, derived))
    us = "" if us_per_call is None else f"{us_per_call:.1f}"
    print(f"{name},{us},{derived}", flush=True)


# ------------------------------------------------------- row schema
# The golden schema every emitted row must satisfy — ``run.py --json``
# validates before writing BENCH_spmm.json and tests/test_bench_schema.py
# re-asserts it on the generated artifact, so bench emitters cannot drift.

def parse_derived(derived: str) -> dict:
    """Parse a row's ``derived`` field: ``;``-separated ``k=v`` entries
    (empty string → ``{}``).  Raises ``ValueError`` on any entry that is
    not of that shape — the contract that keeps BENCH_spmm.json
    machine-readable across benchmark modules."""
    out: dict = {}
    if not derived:
        return out
    for entry in derived.split(";"):
        if not entry:
            continue
        key, eq, val = entry.partition("=")
        if not eq or not key:
            raise ValueError(
                f"derived entry {entry!r} is not k=v (in {derived!r})")
        out[key] = val
    return out


def validate_row(row: dict) -> dict:
    """Assert one JSON row carries exactly ``name``/``us_per_call``/
    ``derived`` with a non-empty name, a finite non-negative time, and a
    parseable derived field; returns ``parse_derived(row['derived'])``.

    Skipped rows (``skipped=...`` in derived) must carry
    ``us_per_call=None`` — and only they may: a timing next to a skip
    annotation reads as a measurement of the skipped thing downstream."""
    if set(row) != {"name", "us_per_call", "derived"}:
        raise ValueError(f"row keys {sorted(row)} != "
                         f"['derived', 'name', 'us_per_call']")
    if not isinstance(row["name"], str) or not row["name"]:
        raise ValueError(f"row name {row['name']!r} must be a non-empty str")
    if not isinstance(row["derived"], str):
        raise ValueError(f"{row['name']}: derived must be a str")
    try:
        parsed = parse_derived(row["derived"])
    except ValueError as e:
        raise ValueError(f"{row['name']}: {e}") from None
    us = row["us_per_call"]
    if "skipped" in parsed:
        if us is not None:
            raise ValueError(
                f"{row['name']}: skipped row must carry us_per_call=None, "
                f"not {us!r} (skipped={parsed['skipped']})")
    elif us is None:
        raise ValueError(f"{row['name']}: us_per_call=None is only legal "
                         "on skipped rows (derived skipped=...)")
    elif not isinstance(us, (int, float)) or isinstance(us, bool) \
            or not np.isfinite(us) or us < 0:
        raise ValueError(f"{row['name']}: us_per_call {us!r} must be a "
                         "finite non-negative number")
    return parsed


@functools.lru_cache(maxsize=4)
def bench_corpus(scale: str = "bench"):
    return corpus(scale)


def subset(graphs, max_nnz=300_000, k=12):
    """Deterministic measurement subset (CPU wall-clock budget)."""
    ok = [g for g in graphs if g.csr.nnz <= max_nnz]
    # spread across families
    fams: dict = {}
    for g in ok:
        fams.setdefault(g.family, []).append(g)
    out, i = [], 0
    while len(out) < min(k, len(ok)):
        for f in sorted(fams):
            if i < len(fams[f]) and len(out) < k:
                out.append(fams[f][i])
        i += 1
    return out


def gflops(csr, dim, seconds):
    return throughput_gflops(csr, dim, seconds)


def count_pallas_calls(fn):
    """Run ``fn`` with the Pallas dispatch intercepted; return the kernel
    names in launch order.  The dispatch is seen while ``fn`` is traced,
    so this counts the launches of one call of ``fn``: JAX's caches are
    cleared first, since a kernel dispatch (a jitted function) whose
    shapes were traced before would re-trace nothing and count none.
    The one shared counter — `tests/test_fusion.py` asserts on it and
    `bench_fusion` records it into BENCH_spmm.json, both through
    ``repro.obs.metrics.intercept_pallas``."""
    from repro.obs.metrics import intercept_pallas

    jax.clear_caches()
    calls: list[str] = []
    with intercept_pallas(calls.append):
        jax.block_until_ready(fn())
    return calls
