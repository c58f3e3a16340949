#!/usr/bin/env python3
"""The control: the reference put in the program's place, in a lower
precision, through the same comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds a,b,c \\
        --seconds <s> [--precisions bf16,high,highest,tau]

For each seed it makes the cell's data exactly as ``bench/run.py`` does
(relations, filters, pools, the window's schedule), answers every row
of every request in the schedule with the reference's forward pass on
the device at each precision (``bf16``: the hidden GEMM in one bfloat16
pass, what the chip does to float32 at default precision; ``high``:
three passes; ``highest``: float32), on the weights the program serves
(a quantized configuration's dequantized), and prints the compared
numbers beside their limits, one JSON line per seed and precision, with
the largest logit error against the float64 reference. ``correct`` must
come out false for ``bf16``. ``tau``, for a quantized configuration, is
``highest`` thresholded at the float32 ``tau`` in place of ``tau_q``;
it must come out false too. No server runs: this reads the control's
numbers, not the program's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                     "src")]


def control_numbers(cfg, mix, seed: int, seconds: float, precisions):
    """``{precision: numbers}`` for one seed (see ``check.compare``)."""
    import numpy as np
    from bench.lib import cell, check, filters, quant, traffic
    made = [cell.make_filter(cfg, seed, r)
            for r in range(int(cfg["relations"]))]
    rels, filts = [m[0] for m in made], [m[1] for m in made]
    pools, is_record = map(list, zip(*[cell.make_pool(rel, mix, seed, r)
                                       for r, rel in enumerate(rels)]))
    tenants = cell.make_tenants(cfg, filts, pools, is_record, seed)
    sched = traffic.schedule(mix, int(cfg["serving"]["tenants"]), seed,
                             seconds)
    logits = cell.pool_models(filts, pools)
    ref = cell.reference(filts, pools, is_record, tenants, sched, logits)
    n = len(sched)
    out = {}
    for prec in precisions:
        lg = [filters.logits_at(f.served, f.cols, rows,
                                "highest" if prec == "tau" else prec)
              for f, rows in zip(filts, pools)]
        got = cell.reference(
            filts, pools, is_record, tenants, sched, lg,
            thresholds=[quant.threshold(f.tau) for f in filts]
            if prec == "tau" else None)
        numbers = check.compare(got.answers, np.ones(n, bool),
                                np.zeros(n, bool), np.arange(n), sched.rows,
                                ref)
        numbers["max_logit_error"] = max(float(np.abs(a - b).max())
                                         for a, b in zip(lg, logits))
        numbers["correct"] = check.verdict(numbers)
        out[prec] = numbers
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precisions", default="bf16,high,highest")
    args = ap.parse_args()
    from bench import run
    from bench.lib import spec, traffic
    bench = spec.load(run.ROOT)
    cellspec = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cellspec["config"], run.ROOT)
    mix = traffic.load(run.BENCH, cellspec["traffic"], cfg["name"])
    run.start_jax(int(cellspec["chips"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control_numbers(cfg, mix, seed, args.seconds,
                              args.precisions.split(","))
        for prec, numbers in res.items():
            print(json.dumps({"seed": seed, "precision": prec, **numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
