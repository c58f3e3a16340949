#!/usr/bin/env python3
"""Run one cell of the benchmark of the grouped filter server.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Set-up makes everything from the seed:
the configuration's relations and their filters (trained on the chip in
one jitted call each), saved as checkpoints that the server admits as
tenants; the traffic's row pools and schedule; then every bucket's
program is warmed. The window drives ``FilterServer.submit_many`` ->
``step()`` -> ``QueryFuture`` for ``--seconds``, the plain reference
checks every answer, and the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), then ``checks``, the numbers
compared beside their limits. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

The run exits non-zero without a result when JAX finds no TPU or fewer
chips than the cell asks for, or outside a checkout of the repository.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DRAIN_S = 60.0          # how long past the window an answer may come
# the end-to-end metrics a run can report (BENCHMARK.json picks)
E2E = ("setup_s", "rows_per_s", "p50_ms", "p90_ms", "device_mib_per_tenant")


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start_jax(chips: int):
    """JAX with the compilation cache in the checkout, on a TPU."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        fail(f"JAX found no TPU (backend {jax.default_backend()!r})", 3)
    if len(jax.devices()) < chips:
        fail(f"the cell needs {chips} chips, JAX sees "
             f"{len(jax.devices())}", 3)
    return jax


def mem(dev, key: str) -> int:
    """A device memory statistic (0 where the backend keeps none)."""
    stats = dev.memory_stats()
    return int(stats.get(key, 0)) if stats else 0


class GcWatch:
    """The garbage collections the window paid for, per generation:
    how many, their seconds, and the longest."""

    def __init__(self):
        self.n, self.s, self.longest, self.t = [0] * 3, [0.0] * 3, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            d = time.perf_counter() - self.t
            g = info["generation"]
            self.n[g] += 1
            self.s[g] += d
            self.longest = max(self.longest, d)

    def close(self) -> str:
        gc.callbacks.remove(self._on)
        return ("window gc " + " ".join(
            f"gen{g} {self.n[g]} {self.s[g] * 1e3:.1f}ms" for g in range(3))
            + f" longest {self.longest * 1e3:.1f}ms")


class Lowerings:
    """Counts programs JAX lowers (a new shape) while armed."""

    def __init__(self, jax):
        from jax._src import dispatch
        self.n = 0
        self.event = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.event:
            self.n += 1


def set_up(cfg, mix, args, timings, jax):
    """Filters, pools, tenants and their checkpoints, the admitted and
    warmed server."""
    from types import SimpleNamespace
    from bench.lib import cell, program, quant
    from repro.serve_filter import FilterServer
    t = time.perf_counter()
    made = [cell.make_filter(cfg, args.seed, r)
            for r in range(int(cfg["relations"]))]
    rels, filts = [m[0] for m in made], [m[1] for m in made]
    timings["fit_s"] = time.perf_counter() - t
    for r, f in enumerate(filts):
        log(f"relation {r}: fixup keys {f.n_keys} m_bits {f.m_bits} "
            f"n_hashes {f.n_hashes}"
            + ("" if f.tau_q is None else f" tau_q {f.tau_q!r} threshold "
               f"logit {f.threshold!r}"))

    t = time.perf_counter()
    pools, is_record = map(list, zip(*[cell.make_pool(rel, mix, args.seed, r)
                                       for r, rel in enumerate(rels)]))
    tenants = cell.make_tenants(cfg, filts, pools, is_record, args.seed)
    timings["pools_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ckpt = tempfile.mkdtemp(prefix="bench-ckpt-")
    names = cell.tenant_names(cfg)
    qm = quant.mode(cfg)
    for i, name in enumerate(names):
        program.save_filter(os.path.join(ckpt, name), tenants.filter_of(
            i, filts[tenants.relation[i]]), qm)
    timings["ckpt_s"] = time.perf_counter() - t

    t = time.perf_counter()
    dev = jax.devices()[0]
    gc.collect()
    b0 = mem(dev, "bytes_in_use")
    server = FilterServer(program.serve_config(cfg, trace=bool(args.trace)))
    program.admit(server, ckpt, names)
    timings["admit_s"] = time.perf_counter() - t
    shutil.rmtree(ckpt)

    t = time.perf_counter()
    program.upload(server)
    gc.collect()
    b1 = mem(dev, "bytes_in_use")
    timings["upload_s"] = time.perf_counter() - t

    t = time.perf_counter()
    program.warm(server, names[0], pools[0])
    timings["warm_s"] = time.perf_counter() - t
    return SimpleNamespace(
        server=server, filts=filts, pools=pools, is_record=is_record,
        tenants=tenants, names=names, rel_of=cell.relation_of(cfg),
        mib=(b1 - b0) / 2 ** 20 / len(names))


def run_window(server, mix, sched, su, args):
    from bench.lib import window
    common = dict(seconds=float(args.seconds), annotate=bool(args.trace),
                  drain_s=DRAIN_S)
    if mix["loop"] == "open":
        return window.run_open(server, sched, su.names, su.pools, su.rel_of,
                               **common)
    return window.run_closed(server, sched, su.names, su.pools, su.rel_of,
                             clients=int(mix["clients"]), **common)



def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro under {ROOT}: run from a checkout of the "
             "repository")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import spec, traffic
    bench = spec.load(ROOT)
    cellspec = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cellspec["config"], ROOT)
    mix = traffic.load(BENCH, cellspec["traffic"], cfg["name"])
    reported = spec.metrics_for(bench, cellspec["name"], bool(args.trace))
    readers = {m["name"]: spec.reader(m["name"], BENCH) for m in reported
               if args.trace}

    timings = {}
    jax = start_jax(int(cellspec["chips"]))
    timings["jax_s"] = time.perf_counter() - T_START
    lowerings = Lowerings(jax)
    out = measure(jax, args, cfg, mix, reported, readers, timings,
                  lowerings)
    print(json.dumps(out), flush=True)
    return 0


def measure(jax, args, cfg, mix, reported, readers, timings,
            lowerings) -> dict:
    """Set up, run the window, check every answer; the result line."""
    import numpy as np
    from bench.lib import cell, check, program, traffic, work, xtrace
    su = set_up(cfg, mix, args, timings, jax)
    server = su.server
    sched = traffic.schedule(mix, len(su.names), args.seed, args.seconds)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    log("setup " + " ".join(f"{k} {v:.3f}" for k, v in timings.items())
        + f" total_s {setup_s:.3f}")

    before = program.serve_totals(server)
    c0, l0 = program.compile_count(), lowerings.n
    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    watch = GcWatch()
    if args.trace:
        program.clear_spans(server)
        jax.profiler.start_trace(prof_dir, profiler_options=xtrace.options())
        with jax.profiler.TraceAnnotation("bench.window"):
            res = run_window(server, mix, sched, su, args)
        jax.profiler.stop_trace()
    else:
        res = run_window(server, mix, sched, su, args)
    log(watch.close())
    c1, l1 = program.compile_count(), lowerings.n
    after = program.serve_totals(server)
    log(f"window compiles: program compile_count +{c1 - c0}, "
        f"lowerings +{l1 - l0}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem(dev, "peak_bytes_in_use")}
    serve = {k: after[k] - before[k] for k in after}
    spans, dropped = program.program_spans(server, res.t0, res.t_end)
    server.close()
    del server, su.server
    gc.collect()

    t = time.perf_counter()
    ref = cell.reference(su.filts, su.pools, su.is_record, su.tenants, sched,
                         cell.pool_models(su.filts, su.pools))
    sent = res.sent
    resolved = ~np.isnan(res.t_done[:sent])
    numbers = check.compare(res.answers, resolved, res.failed[:sent],
                            res.sched_idx, sched.rows, ref)
    numbers["unanswered_requests"] += len(sched) - sent \
        if mix["loop"] == "open" else 0
    log(f"reference_s {time.perf_counter() - t:.3f}")

    window_s = res.t_end - res.t0
    rows = int(sched.rows[res.sched_idx][res.counted[:sent]].sum())
    ctx = {"loop": mix["loop"], "window_s": window_s, "rows": rows,
           "latency_s": res.latency, "late_s": res.late, "serve": serve,
           "spans": spans, "spans_dropped": dropped,
           "t0": res.t0, "t1": res.t_end, "trace": None,
           "work": work.per_row(cfg),
           "peaks": work.peaks(dev.device_kind) if args.trace else None}
    out = {"correct": check.verdict(numbers) and c1 == c0 and l1 == l0,
           "attempted": int(len(sched) if mix["loop"] == "open" else sent),
           "failed": int(numbers["unanswered_requests"])}
    metrics = {}
    if args.trace:
        path = find_xplane(prof_dir)
        t = time.perf_counter()
        red = xtrace.reduce_file(path) if path else None
        log(f"trace {os.path.getsize(path) if path else 0} bytes, "
            f"reduced in {time.perf_counter() - t:.3f} s")
        shutil.rmtree(prof_dir, ignore_errors=True)
        if red is None or (serve["valid_rows"] and not work.program_s(red)):
            fail("the traced window holds no device op of the grouped "
                 f"program (modules {sorted(red['module_s']) if red else []}"
                 f", looked for {list(work.PROGRAM_MODULES)})", 4)
        ctx["trace"] = red
        for m in reported:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    else:
        e2e = {"setup_s": setup_s, "rows_per_s": rows / window_s,
               "device_mib_per_tenant": su.mib}
        if res.latency is not None:
            lat = res.latency[:sent][resolved] * 1e3
            e2e["p50_ms"] = float(np.percentile(lat, 50))
            e2e["p90_ms"] = float(np.percentile(lat, 90))
        for m in reported:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    if res.latency is not None and resolved.any():
        lat = res.latency[:sent][resolved] * 1e3
        q = np.percentile(lat, [50, 90, 99, 99.9, 100])
        log("window latency_ms p50 {:.3f} p90 {:.3f} p99 {:.3f} p99.9 "
            "{:.3f} max {:.3f}; submit late p99 {:.3f}".format(
                *q, float(np.nanpercentile(res.late[:sent], 99)) * 1e3))
    if res.latency is None and window_s >= 2:
        # a closed loop's pace, second by second: steady or stalled
        done = res.t_done[res.counted] - res.t0
        per_s = np.bincount(done.astype(int), weights=sched.rows[
            res.sched_idx[res.counted]])[:int(window_s)]
        log("window rows per second: min {:.0f} median {:.0f} max {:.0f}"
            .format(per_s.min(), np.median(per_s), per_s.max()))
    log(f"window requests {sent} rows {rows} window_s {window_s:.6f} "
        f"valid_rows {serve['valid_rows']} padded_rows "
        f"{serve['padded_rows']} batches {serve['batches']}")
    out["metrics"] = metrics
    out["device"] = device
    if ctx["trace"] is not None:
        out["breakdown"] = {"device_ops": ctx["trace"]["top_ops"],
                            "idle_gaps": ctx["trace"]["idle_gaps"]}
    out["checks"] = {k: {"value": numbers[k], "limit": v}
                     for k, v in check.LIMITS.items()}
    out["checks"]["window_compiles"] = {"value": (c1 - c0) + (l1 - l0),
                                        "limit": 0}
    for line in check.lines(numbers):
        log(line)
    log(f"check window_compiles {(c1 - c0) + (l1 - l0)} limit 0")
    return out


def find_xplane(root: str):
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    return None


if __name__ == "__main__":
    sys.exit(main())
