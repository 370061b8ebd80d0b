"""What every cell shares: finding its pieces by name, the device, the
compile cache, and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Each piece is a file of its own,
found by name: ``configs/<config>.json``, ``mixes/<traffic>.json``,
``limits/<cell>.json`` (the limits of its correctness check) and, for
each per-layer metric, the reader ``metrics/<metric>.py``.  The mix's
``kind`` names the general runner of its traffic, ``kinds/<kind>.py``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def piece(kind: str, name: str, base: str = HERE) -> dict:
    """``<base>/<kind>/<name>.json``: a configuration, mix or limits."""
    return load_json(os.path.join(base, kind, f"{name}.json"))


def runner(kind: str):
    return importlib.import_module(f"chipbench.kinds.{kind}")


def reader(metric: str, base: str = HERE):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(base, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end_of(spec: dict, cell: str) -> list:
    """End-to-end metrics the cell reports: those that list it, and those
    that list no cells (``setup_s``)."""
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_of(spec: dict, cell: str) -> list:
    """Per-layer metrics the cell reports: those whose ``workloads``
    list it."""
    return [m for m in spec["per_layer"] if cell in m["workloads"]]


def devices(chips: int):
    """The accelerator's devices; raises ``NoChip`` without enough."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"JAX finds no accelerator (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


CACHE_ENTRY_BYTES = 4 << 30


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else ``.jax_cache/`` at the root of the checkout; every program
    is cached, however fast it compiled.  A size cap under 4 GiB is
    raised to it: the program embeds its PCSR packs in the compiled step
    as constants, and the arxiv-sized GCN step alone serialises to about
    287 MB, which a smaller cap refuses to store."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cap = jax.config.jax_compilation_cache_max_size
    if 0 < cap < CACHE_ENTRY_BYTES:
        jax.config.update("jax_compilation_cache_max_size",
                          CACHE_ENTRY_BYTES)
    return path


def check(value: float, limit: float) -> dict:
    return {"value": value, "limit": limit}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
