#!/usr/bin/env python3
"""Readings that set a training cell's correctness limits (not run by the
benchmark).

    python chipbench/control.py --workload <cell> --seconds <s> --seeds a b c ...

For each seed, in one process: a sound run of the cell (set-up, a window
of ``--seconds``, the comparison with the reference), printing every
number the comparison can make.  On the first ``--controls`` seeds it
also reads:

* the control: the reference computed one precision step below the
  configuration's (``high``, three bf16 passes, for float32 at
  ``highest``), put in the program's place;
* the faults a training cell can have: the loss taken over half of the
  training nodes, and a step that returns its state unchanged (which
  reads 1 by construction and needs no run).

Each seed prints one JSON line ``{"seed", "sound", "e2e", "control",
"faults"}`` of plain numbers; PERF.md records them with the limits set
from them.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import compare, graphs, harness  # noqa: E402

# no limit: every number the comparison can make is read
ALL_NUMBERS = dict.fromkeys(
    ["loss_gap", "loss1_gap", "grad_gap", "update_gap", "update1_gap"],
    float("inf"))


def numbers(prog: dict, ref: dict) -> dict:
    return {k: c["value"] for k, c in
            compare.train_checks(prog, ref, ALL_NUMBERS).items()}


def control_and_faults(cfg, mix, seed, ref) -> tuple:
    import jax.numpy as jnp
    from chipbench.kinds import train
    u, v = graphs.graph_of(cfg)
    steps = mix["checked_steps"]
    high = train.reference_readings(cfg, seed, u, v, steps, "high")
    n_half = cfg["graph"]["train_nodes"] // 2

    def half(mask):   # keep the first half of the training nodes
        keep = (jnp.cumsum(mask) <= n_half).astype(mask.dtype)
        return mask * keep
    halved = train.reference_readings(cfg, seed, u, v, steps,
                                      mask_fn=half)
    # a step that returns its state unchanged: no moment, no change, the
    # first loss again at every step
    still = {"losses": [ref["losses"][0]] * steps,
             "grad_norms": [0.0] * len(ref["grad_norms"]),
             "delta1_norms": [0.0] * len(ref["delta1_norms"]),
             "delta_norms": [0.0] * len(ref["delta_norms"])}
    return numbers(high, ref), {"half_batch": numbers(halved, ref),
                                "state_unchanged": numbers(still, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    try:
        devs = harness.devices(cell["chips"])
    except harness.NoChip as e:
        harness.log(f"control: {e}; nothing run")
        return 2
    harness.enable_compile_cache()
    cfg = harness.piece("configs", cell["config"])
    mix = harness.piece("mixes", cell["traffic"])
    drv = harness.runner(mix["kind"])
    for k, seed in enumerate(args.seeds):
        t = time.perf_counter()
        res = drv.run(cfg, mix, ALL_NUMBERS, seed=seed,
                      seconds=args.seconds, trace=False, devs=devs, t0=t,
                      out_dir=os.path.join(ROOT, "chipbench_out", "control"))
        ref = res["readings"]["ref"]
        line = {"seed": seed, "sound": numbers(res["readings"]["prog"], ref),
                "e2e": res["e2e"]}
        if k < args.controls:
            line["control"], line["faults"] = control_and_faults(
                cfg, mix, seed, ref)
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
