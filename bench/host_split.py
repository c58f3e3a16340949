#!/usr/bin/env python3
"""One traced window of a cell, with the device's idle time split by
what the program's host code was doing.

    python3 bench/host_split.py --workload <cell> --seed <n> --seconds <s>
        [--keep OUT.xplane.pb]

from the root of a checkout, on the chip. Set-up and window are those of
``bench/run.py --trace 1`` (the server's tracer on, the profiler
recording the window); the answers are not checked against the
reference (``run.py`` does that). The last line of standard output is
one JSON object:

* ``window``: the window's seconds, rows, valid rows and batches, its
  latency quantiles (open loop) or rows per second (closed loop);
* ``harness``: ``device_idle_share``, ``host_busy_share`` and the four
  stage readers (``<stage>_us_per_krow``), as ``run.py`` reads them;
* ``ring``: spans recorded into the tracer's ring and spans dropped;
* ``split``: ``bench/lib/hostsplit.py``'s reduction of the trace
  (``clock_offset_us``, causal pairs, ``host_self_s``,
  ``idle_by_span``, ``stalls``) or ``null`` for a program that writes
  no ``serve.*`` annotations;
* ``metrics``: from the split and the program's counters, per 1000
  valid rows each phase's self time with its nested spans
  (``submit``/``prepare``/``dispatch``/``block``/``retire``), the
  shares of the window in which the device idled while the host was in
  ``serve.device_block`` or in another ``serve.*`` span, the share of
  grouped dispatches whose tile gather the arena's cache spared, and the
  90th percentile of the window's queue waits (submit to first
  dispatch);
* ``checks``: the split's idle shares against ``device_idle_share``,
  the four stages against ``host_busy_share``, and the largest causal
  pair still broken after the clock shift.

The fields that need what a program lacks (no annotations, no tile
counters, no ``Histogram.since``) are ``null``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
STAGES = ("prepare", "dispatch", "block", "retire")


def _tiles(server):
    """(hits, misses) of the arenas' tile caches, or None."""
    arenas = list(server.registry.groups.values())
    if not all(hasattr(a, "tile_hits") for a in arenas):
        return None
    return (sum(a.tile_hits for a in arenas),
            sum(a.tile_misses for a in arenas))


def split_run(jax, args, cfg, mix, keep=None) -> dict:
    import numpy as np
    from bench import run
    from bench.lib import hostsplit, program, spec, traffic, work, xtrace
    su = run.set_up(cfg, mix, SimpleNamespace(seed=args.seed, trace=1),
                    {}, jax)
    server = su.server
    sched = traffic.schedule(mix, len(su.names), args.seed, args.seconds)
    gc.collect()
    queue = server.stats.queue_time
    q0 = queue.copy() if hasattr(queue, "since") else None
    tiles0, before = _tiles(server), program.serve_totals(server)
    program.clear_spans(server)
    prof_dir = tempfile.mkdtemp(prefix="bench-split-")
    jax.profiler.start_trace(prof_dir, profiler_options=xtrace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        res = run.run_window(server, mix, sched, su, SimpleNamespace(
            seconds=args.seconds, trace=1))
    jax.profiler.stop_trace()
    tiles1, after = _tiles(server), program.serve_totals(server)
    serve = {k: after[k] - before[k] for k in after}
    spans, dropped = program.program_spans(server, res.t0, res.t_end)
    recorded = len(server.tracer) + dropped
    queue_p90 = (queue.since(q0).percentile(90) * 1e3
                 if q0 is not None else None)
    server.close()
    path = run.find_xplane(prof_dir)
    red = xtrace.reduce_file(path) if path else None
    split = hostsplit.reduce_file(path) if path else None
    if keep and path:
        shutil.copy(path, keep)
    shutil.rmtree(prof_dir, ignore_errors=True)

    window_s = res.t_end - res.t0
    sent = res.sent
    rows = int(sched.rows[res.sched_idx][res.counted[:sent]].sum())
    win = {"window_s": window_s, "rows": rows, **serve,
           "rows_per_s": rows / window_s}
    if res.latency is not None:
        lat = res.latency[:sent][~np.isnan(res.t_done[:sent])] * 1e3
        win.update(zip(("p50_ms", "p90_ms", "p99_ms"),
                       map(float, np.percentile(lat, [50, 90, 99]))))
    ctx = {"spans": spans, "spans_dropped": dropped, "t0": res.t0,
           "t1": res.t_end, "serve": serve, "trace": red,
           "work": work.per_row(cfg)}
    names = ["device_idle_share", "host_busy_share"] + [
        f"{s}_us_per_krow" for s in STAGES]
    harness = {n: spec.reader(n)(ctx) for n in names}

    metrics = {"queue_wait_p90_ms": queue_p90,
               "tile_cache_hit_share": None}
    if tiles0 is not None and tiles1 is not None:
        hits, misses = (b - a for a, b in zip(tiles0, tiles1))
        if hits + misses:
            metrics["tile_cache_hit_share"] = 100.0 * hits / (hits + misses)
        metrics["tile_dispatches"] = hits + misses
    checks = {}
    if split is not None and serve["valid_rows"]:
        metrics.update({f"{p}_us_per_krow": v for p, v in
                        hostsplit.phase_us_per_krow(
                            split, serve["valid_rows"]).items()})
        metrics.update({f"{k}_share": v
                        for k, v in hostsplit.idle_shares(split).items()})
        if harness["device_idle_share"] is not None:
            checks["idle_split_le_idle_share"] = bool(
                metrics["idle_in_program_share"]
                + metrics["idle_in_block_share"]
                <= harness["device_idle_share"] + 1e-9)
        checks["causal_broken_us"] = split["causal_broken_us"]
    if harness["host_busy_share"] is not None and not dropped:
        stages = sum(harness[f"{s}_us_per_krow"] or 0.0 for s in STAGES)
        union = harness["host_busy_share"] / 100 * window_s
        checks["stages_over_host_union"] = (
            stages * 1e-6 * serve["valid_rows"] / 1e3 / union)
    dev = jax.devices()[0]
    return {"cell": args.workload, "seed": args.seed,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "window": win, "harness": harness,
            "ring": {"recorded": recorded, "dropped": dropped,
                     "maxlen": server.tracer.maxlen},
            "split": split, "metrics": metrics, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the trace (.xplane.pb) here")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    from bench.lib import spec, traffic
    bench = spec.load(ROOT)
    cellspec = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cellspec["config"], ROOT)
    mix = traffic.load(BENCH, cellspec["traffic"], cfg["name"])
    jax = run.start_jax(int(cellspec["chips"]))
    out = split_run(jax, args, cfg, mix, keep=args.keep)
    # NumPy scalars (the reductions' sums) as plain numbers
    print(json.dumps(out, default=lambda x: x.item()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
