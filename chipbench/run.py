#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits and per-layer metric readers are
found by name under ``chipbench/`` (see ``harness.py``).  Set-up builds
everything from the seed and warms every shape the cell uses; the window
then measures for ``--seconds``.  After the window the run checks what
the timed path produced against the plain reference (``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit, which also end standard error.  Without an accelerator, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def run_cell(spec: dict, name: str, *, seed: int, seconds: float,
             trace: bool, devs, t0: float, base: str = harness.HERE,
             **run_kw) -> dict:
    """Run cell ``name`` on ``devs``; returns the result object."""
    cell = harness.find(spec["workloads"], name, "workload")
    cfg = harness.piece("configs", cell["config"], base)
    mix = harness.piece("mixes", cell["traffic"], base)
    limits = harness.piece("limits", name, base)
    out_dir = os.path.join(harness.ROOT, "chipbench_out", name)
    res = harness.runner(mix["kind"]).run(
        cfg, mix, limits, seed=seed, seconds=seconds, trace=trace,
        devs=devs, t0=t0, out_dir=out_dir, **run_kw)
    device = harness.device_info(devs)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    units = {m["name"]: m["unit"] for m in
             spec["end_to_end"] + spec["per_layer"]}
    if trace:
        red = res["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        values = {}
        for m in harness.per_layer_of(spec, name):
            v = harness.reader(m["name"], base)(res["ctx"])
            if v is not None:
                values[m["name"]] = v
    else:
        values = {m["name"]: res["e2e"][m["name"]]
                  for m in harness.end_to_end_of(spec, name)}
    return {"correct": harness.passed(res["checks"]),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device,
            "breakdown": res.get("breakdown") if trace else None,
            "checks": res["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    try:
        devs = harness.devices(cell["chips"])
    except harness.NoChip as e:
        harness.log(f"chipbench: {e}; nothing run")
        return 2
    harness.log(f"chipbench: {args.workload} seed {args.seed} on "
                f"{len(devs)} x {devs[0].device_kind}; compile cache "
                f"{harness.enable_compile_cache()}")
    out = run_cell(spec, args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace), devs=devs,
                   t0=T0)
    harness.print_checks(out["checks"])
    print(harness.result_line(**out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
