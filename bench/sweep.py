#!/usr/bin/env python3
"""Find an open-loop cell's knee: several offered rates on one set-up.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 200000,400000,...

Sets the cell up once (as ``bench/run.py`` does), then for each rate
runs the cell's open loop for ``--seconds`` at that rate and prints one
JSON line: offered and achieved rows/s, how long the last requests took
to drain after the schedule ended, p50/p99 latency from the due time,
and the submit lateness. A rate is sustained when the drain stays near
one step and the achieved rate matches the offered one; the knee is
the highest such rate (``--refine`` halves the interval between the
highest sustained and the lowest unsustained rate that many times).
The last line is ``{"knee_rows_per_s": ...}``. Used once per cell, to
set the rate in ``bench/traffic/<mix>/<config>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                     "src")]

from bench import run  # noqa: E402

# a rate is sustained when its last requests drain within this much
# past the schedule's end (a few steps)
DRAIN_OK_MS = 50.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--refine", type=int, default=0)
    args = ap.parse_args()
    args.trace = 0
    import numpy as np
    from bench.lib import spec, traffic, window
    bench = spec.load(run.ROOT)
    cellspec = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cellspec["config"], run.ROOT)
    mix = traffic.load(run.BENCH, cellspec["traffic"], cfg["name"])
    if mix["loop"] != "open":
        run.fail("a knee is swept on an open-loop cell")
    jax = run.start_jax(int(cellspec["chips"]))
    timings = {}
    su = run.set_up(cfg, mix, args, timings, jax)
    server = su.server
    run.log("setup " + " ".join(f"{k} {v:.3f}" for k, v in timings.items()))
    gc.collect()

    def offer(k, rate):
        sched = traffic.schedule(dict(mix, rate_rows_per_s=rate),
                                 len(su.names),
                                 args.seed + k, args.seconds)
        res = window.run_open(server, sched, su.names, su.pools, su.rel_of,
                              seconds=args.seconds, annotate=False,
                              drain_s=run.DRAIN_S)
        done = ~np.isnan(res.t_done)
        lat = res.latency[done] * 1e3
        row = {
            "offered_rows_per_s": rate,
            "achieved_rows_per_s": float(sched.rows[done].sum()
                                         / (res.t_end - res.t0)),
            "drain_ms": (res.t_end - res.t0 - args.seconds) * 1e3,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "late_p99_ms": float(np.nanpercentile(res.late, 99)) * 1e3,
            "requests": len(sched), "unresolved": int((~done).sum())}
        print(json.dumps(row), flush=True)
        server.run_until_drained()
        time.sleep(0.5)
        return (row["unresolved"] == 0 and row["drain_ms"] < DRAIN_OK_MS
                and row["achieved_rows_per_s"] >= 0.98 * rate)

    rates = [float(r) for r in args.rates.split(",")]
    ok = [offer(k, r) for k, r in enumerate(rates)]
    good = max((r for r, o in zip(rates, ok) if o), default=0.0)
    bad = min((r for r, o in zip(rates, ok) if not o and r > good),
              default=None)
    for k in range(args.refine):
        if bad is None:
            break
        mid = (good + bad) / 2
        if offer(len(rates) + k, mid):
            good = mid
        else:
            bad = mid
    print(json.dumps({"knee_rows_per_s": good}), flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
