"""A benchmark directory of tiny cells for CPU rehearsals.

``make(base)`` writes, under ``base``, shrunken copies of the real
configurations and mixes, the real limits under the tiny cells' names,
every real metric reader, and one throw-away configuration, mix and
metric reader that no file of the harness names; it returns a spec in
``BENCHMARK.json``'s form whose cells run in seconds on the CPU with the
program's Pallas kernels in interpret mode.
"""
import json
import os
import shutil

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
TINY_GRAPH = {"nodes": 300, "edges": 1200, "train_nodes": 120}
CELLS = {  # tiny cell -> (real cell, configuration changes)
    "tiny-gcn.train": ("dpgat8x8-pubmed.train",
                       {"kind": "gcn", "in_features": 24, "hidden": 8,
                        "classes": 12, "heads": 1}),
    "tiny-dpgat.train": ("dpgat8x8-pubmed.train",
                         {"in_features": 24, "hidden": 16, "heads": 2,
                          "classes": 5}),
}
THROWAWAY_METRIC = '''"""Counts the window's training steps (a throw-away metric)."""


def read(ctx):
    return ctx.get("steps")
'''


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make(base: str) -> dict:
    spec = _load(SPEC)
    shutil.copytree(os.path.join(HERE, "metrics"),
                    os.path.join(base, "metrics"))
    cells = []
    for tiny, (real, change) in CELLS.items():
        cell = dict(next(w for w in spec["workloads"] if w["name"] == real))
        cfg = _load(os.path.join(HERE, "configs", f"{cell['config']}.json"))
        cfg["model"].update(change)
        cfg["graph"].update(TINY_GRAPH)
        name = tiny.split(".")[0]
        _dump(cfg, os.path.join(base, "configs", f"{name}.json"))
        mix = _load(os.path.join(HERE, "mixes", f"{cell['traffic']}.json"))
        _dump(mix, os.path.join(base, "mixes", f"tiny-{cell['traffic']}.json"))
        _dump(_load(os.path.join(HERE, "limits", f"{real}.json")),
              os.path.join(base, "limits", f"{tiny}.json"))
        cells.append(dict(cell, name=tiny, config=name,
                          traffic=f"tiny-{cell['traffic']}"))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"] = m["workloads"] + [tiny]
    # a throw-away configuration, mix and metric, found by name alone
    cfg = _load(os.path.join(base, "configs", "tiny-gcn.json"))
    cfg["model"].update(hidden=8, layers=3)
    _dump(cfg, os.path.join(base, "configs", "throwaway.json"))
    _dump({"kind": "train", "checked_steps": 3},
          os.path.join(base, "mixes", "throwaway-mix.json"))
    _dump(_load(os.path.join(base, "limits", "tiny-gcn.train.json")),
          os.path.join(base, "limits", "throwaway.cell.json"))
    with open(os.path.join(base, "metrics", "throwaway_steps.py"), "w") as f:
        f.write(THROWAWAY_METRIC)
    cells.append({"name": "throwaway.cell", "config": "throwaway",
                  "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "step_s":
            m["workloads"].append("throwaway.cell")
    spec["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "step", "moves": "step_s",
        "workloads": ["throwaway.cell"]})
    spec["workloads"] = cells
    return spec


def run(spec: dict, base: str, cell: str, *, trace: bool = False,
        seed: int = 2**31 + 7, seconds: float = 0.3) -> dict:
    """One run of ``cell`` on the CPU, skipping the look for a chip."""
    import time

    import jax

    from chipbench import peaks
    from chipbench.run import run_cell
    peaks.PEAKS.setdefault("cpu", {"flops_bf16": 1e12,
                                   "hbm_bytes_per_s": 1e11})
    return run_cell(spec, cell, seed=seed, seconds=seconds, trace=trace,
                    devs=jax.devices(), t0=time.perf_counter(), base=base,
                    backend="pallas", interpret=True)
