#!/usr/bin/env python3
"""Drive the grouped filter server once on a TPU, at the paper's widths.

The served path end to end, through the entry points a user calls:
``existence.fit`` -> ``existence.save_index`` (a v2 checkpoint) ->
``FilterServer.admit(TenantSpec(checkpoint=...))`` -> grouped
``PlanGroupArena`` -> ``QueryScheduler`` -> ``GroupedExecutor`` ->
``QueryFuture.result``.

Default run (one chip): fit the airplane (theta=5500) and DMV (theta=100)
bases of the paper's Table 1 on 100 000 synthesized records each, save
each base, admit 32 tenants of each from its checkpoint into four
servers (grouped fp32, grouped int8, grouped int4-NF4, ungrouped fp32)
and serve a few thousand requests of 16, 256 and 4096 rows. The run
fails unless every indexed record answers True in every server (zero
false negatives against ``TupleDataset.contains``), no tenant is
DEGRADED and no program compiles inside the counted window. FPR, the
grouped-versus-ungrouped disagreement count, arena bytes next to the
device's bytes in use, compile telemetry and wall time are printed.

``--chips 4`` runs only the sharded path: a grouped fp32 server whose
arenas are sharded over a 4-device mesh, compared row by row with a
grouped server on one device of the same process.

The last line of stdout is ``{"ok": true, "device": {...}}``. The script
exits non-zero without that line when JAX finds no TPU, when it is not
run from a checkout of the repository, or when a check fails.

Usage: python chip_smoke.py [--chips 1|4] [--seed N] [--out DIR]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

BUCKETS = (64, 256, 1024, 4096)
# (base name, theta): Table 1 rows of configs/clmbf.py
BASES = (("airplane", 5500), ("dmv", 100))
COPIES = 32                       # tenants admitted per base
N_RECORDS = 100_000
N_NONMEMBERS = 100_000
N_SAMPLE = 4096                   # member rows checked on each other copy
# one tenant's requests in the counted window: 16-row requests coalesce
# into megabatches; 256 and 4096 rows fill their own buckets
REQUEST_MIX = (16,) * 40 + (256,) * 4 + (4096,)


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def fit_bases(seed: int, n_records: int = N_RECORDS, settings=None):
    """Fit each base on the default device: ``{name: (ds, index)}``."""
    from repro.configs import clmbf
    from repro.core import existence
    from repro.data import tuples
    out = {}
    for name, theta in BASES:
        cards = clmbf.CLMBFExperiment(name, theta).cards
        ds = tuples.synthesize(cards, n_records, seed=seed)
        t0 = time.perf_counter()
        idx = existence.fit(ds, theta=theta, hidden=(64,),
                            settings=settings)
        dt = time.perf_counter() - t0
        fx = idx.fixup_filter
        print(f"fit {name}: theta={theta} columns={len(cards)} "
              f"input_dim={idx.cfg.plan.input_dim} "
              f"nn_params={idx.memory.nn_params} records={len(ds.records)} "
              f"accuracy={idx.train_log['accuracy']:.4f} "
              f"fixup_keys={fx.n_false_negatives} m_bits={fx.params.m_bits} "
              f"fit_s={dt:.1f}", flush=True)
        out[name] = (ds, idx)
    return out


def save_bases(bases, out_dir: str, copies: int) -> str:
    """Save each base once and name ``copies`` tenants after it; returns
    the checkpoint root a ``TenantSpec(checkpoint=...)`` hydrates from."""
    from repro.core import existence
    root = os.path.join(out_dir, "ckpt")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for name, (_, idx) in bases.items():
        existence.save_index(os.path.join(root, name), idx, step=0)
        for k in range(copies):
            os.symlink(name, os.path.join(root, _tenant(name, k)))
    return root


def _tenant(base: str, k: int) -> str:
    return f"{base}-{k:02d}"


def make_queries(bases, seed: int, copies: int,
                 n_nonmembers: int = N_NONMEMBERS,
                 n_sample: int = N_SAMPLE, mix=REQUEST_MIX):
    """The requests every server answers, as ``(tenant, ids, members)``
    lists: ``window`` (the counted traffic, half members), ``members``
    (every record on copy 0, a seeded sample on every other copy) and
    ``nonmembers`` (rows ``TupleDataset.contains`` rejects, on copy 0)."""
    import numpy as np
    from repro.data import tuples
    rng = np.random.default_rng(seed + 1)
    window, members, nonmembers = [], [], []
    for bi, (name, (ds, _)) in enumerate(bases.items()):
        neg = tuples.sample_negatives(ds, n_nonmembers, seed + 2 + bi,
                                      wildcard_prob=0.0)
        neg = neg[~ds.contains(neg)]
        nonmembers.append((_tenant(name, 0), neg, False))
        members.append((_tenant(name, 0), ds.records, True))
        for k in range(1, copies):
            pick = rng.integers(0, len(ds.records), n_sample)
            members.append((_tenant(name, k), ds.records[pick], True))
        for k in range(copies):
            for n in mix:
                half = n // 2
                rows = np.concatenate(
                    [ds.records[rng.integers(0, len(ds.records), half)],
                     neg[rng.integers(0, len(neg), n - half)]])
                window.append((_tenant(name, k), rows, None))
    order = rng.permutation(len(window))
    window = [window[i] for i in order]
    return window, members, nonmembers


def _answer(srv, requests):
    """Submit ``requests`` in one ``submit_many``; answers per request."""
    import numpy as np
    futs = srv.submit_many([(t, ids) for t, ids, _ in requests])
    srv.run_until_drained()
    return [np.asarray(f.result()) for f in futs]


def _bytes_in_use(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


def _admit(srv, bases, ckpt: str, copies: int) -> None:
    from repro.serve_filter import TenantSpec
    for name in bases:
        for k in range(copies):
            srv.admit(TenantSpec(_tenant(name, k), checkpoint=ckpt))


def _materialize_arenas(srv) -> None:
    """Upload every arena's device views now (they are built lazily at
    the first dispatch), so their bytes can be read off the device."""
    import jax
    for arena in srv.registry.groups.values():
        jax.block_until_ready(arena.device_arrays())


def serve_flavor(label: str, config, bases, ckpt: str, copies: int,
                 queries):
    """Admit, warm, run the counted window and the checks on one server;
    returns its answers, all requests concatenated. Exits on a failed
    check."""
    import jax
    import numpy as np
    from repro.serve_filter import FilterServer, TenantState
    from repro.serve_filter import executors
    window, members, nonmembers = queries
    dev = jax.devices()[0]
    gc.collect()    # servers hold reference cycles: free the last one's
    with FilterServer(config) as srv:
        b0 = _bytes_in_use(dev)
        t0 = time.perf_counter()
        _admit(srv, bases, ckpt, copies)
        admit_s = time.perf_counter() - t0
        b1 = _bytes_in_use(dev)
        _materialize_arenas(srv)
        b2 = _bytes_in_use(dev)

        # warm every bucket for each plan group (one tenant alone fills
        # exactly the bucket its row count rounds up to)
        for name, (ds, _) in bases.items():
            for n in BUCKETS:
                _answer(srv, [(_tenant(name, 0), ds.records[:n], True)])
        c0 = executors.compile_count()
        t0 = time.perf_counter()
        win = _answer(srv, window)
        win_s = time.perf_counter() - t0
        c1 = executors.compile_count()
        mem = _answer(srv, members)
        non = _answer(srv, nonmembers)
        c2 = executors.compile_count()
        snap = srv.stats_snapshot()
        states = {t: srv.registry.state_of(t) for t in srv.registry.tenants}

    fn = {name: 0 for name in bases}
    for (tenant, _, _), ans in zip(members, mem):
        fn[tenant.rsplit("-", 1)[0]] += int((~ans).sum())
    fpr = {tenant.rsplit("-", 1)[0]: (int(ans.sum()), len(ans))
           for (tenant, _, _), ans in zip(nonmembers, non)}
    n_members = sum(len(ids) for _, ids, _ in members)
    win_rows = sum(len(ids) for _, ids, _ in window)
    degraded = sorted(t for t, s in states.items()
                      if s is TenantState.DEGRADED)
    print(f"[{label}] false negatives: {sum(fn.values())} of {n_members} "
          f"member rows ({', '.join(f'{k} {v}' for k, v in fn.items())})")
    print(f"[{label}] FPR over seeded non-members: " + ", ".join(
        f"{k} {hit / n:.6f} ({hit}/{n})" for k, (hit, n) in fpr.items()))
    print(f"[{label}] arena_mb={snap['arena_mb']:.3f} | device bytes: "
          f"arena views {(b2 - b1) / 2**20:.3f} MiB, tenants admitted "
          f"{(b1 - b0) / 2**20:.3f} MiB, bytes_in_use after upload "
          f"{b2 / 2**20:.3f} MiB")
    print(f"[{label}] compile_count={int(snap['compile_count'])} "
          f"compile_ms_total={snap['compile_ms_total']:.1f} "
          f"(counted window +{c1 - c0}, checks +{c2 - c1}) "
          f"tenants={len(states)} degraded={len(degraded)} "
          f"plan_groups={int(snap['plan_groups'])} "
          f"grouped_batches={int(snap['grouped_batches'])}")
    print(f"[{label}] smoke timing, not a benchmark: counted window "
          f"{len(window)} requests / {win_rows} rows in {win_s:.3f} s "
          f"wall; admit {admit_s:.1f} s; rows answered in all "
          f"{win_rows + n_members + sum(n for _, n in fpr.values())}",
          flush=True)
    if sum(fn.values()):
        _fail(f"{label}: {sum(fn.values())} false negatives")
    if degraded:
        _fail(f"{label}: DEGRADED tenants {degraded}")
    if c1 != c0:
        _fail(f"{label}: {c1 - c0} compiles inside the counted window")
    return np.concatenate(win + mem + non)


def _configs():
    from repro.serve_filter import ServeConfig
    from repro.serve_filter.config import (BucketConfig, GroupingConfig,
                                           QuantConfig)
    buckets = BucketConfig(BUCKETS)
    grouped = GroupingConfig(enabled=True)
    return (
        ("fp32-grouped", ServeConfig(buckets=buckets, grouping=grouped)),
        ("int8-grouped", ServeConfig(
            buckets=buckets, grouping=grouped,
            quant=QuantConfig(enabled=True, bits=8))),
        ("int4nf4-grouped", ServeConfig(
            buckets=buckets, grouping=grouped,
            quant=QuantConfig(enabled=True, bits=4, grid="nf4"))),
        ("fp32-ungrouped", ServeConfig(buckets=buckets)),
    )


def run_default(bases, ckpt: str, queries, copies: int = COPIES) -> None:
    results = {label: serve_flavor(label, config, bases, ckpt, copies,
                                   queries)
               for label, config in _configs()}
    a, b = results["fp32-grouped"], results["fp32-ungrouped"]
    print(f"grouped vs ungrouped fp32: {int((a != b).sum())} of {len(a)} "
          f"rows disagree")


def run_mesh(bases, ckpt: str, queries, copies: int = COPIES,
             n_devices: int = 4) -> None:
    """A grouped fp32 server with arenas sharded over ``n_devices``,
    checked row by row against a grouped server on one device."""
    import jax
    import numpy as np
    from repro.serve_filter import FilterServer, ServeConfig
    from repro.serve_filter.config import (BucketConfig, GroupingConfig,
                                           PlacementConfig)
    _, members, nonmembers = queries
    mesh = jax.make_mesh((n_devices,), ("data",))
    common = dict(buckets=BucketConfig(BUCKETS),
                  grouping=GroupingConfig(enabled=True))
    answers = {}
    for label, config in (
            ("fp32-grouped-sharded",
             ServeConfig(placement=PlacementConfig(mesh=mesh), **common)),
            ("fp32-grouped-local", ServeConfig(**common))):
        with FilterServer(config) as srv:
            _admit(srv, bases, ckpt, copies)
            _materialize_arenas(srv)
            used = [_bytes_in_use(d) for d in mesh.devices.flat]
            t0 = time.perf_counter()
            mem = _answer(srv, members)
            non = _answer(srv, nonmembers)
            dt = time.perf_counter() - t0
            snap = srv.stats_snapshot()
        n_fn = sum(int((~ans).sum()) for ans in mem)
        n_fp = sum(int(ans.sum()) for ans in non)
        n_non = sum(len(ans) for ans in non)
        print(f"[{label}] false negatives: {n_fn} of "
              f"{sum(len(a) for a in mem)} member rows; FPR "
              f"{n_fp / n_non:.6f} ({n_fp}/{n_non})")
        print(f"[{label}] arena_mb={snap['arena_mb']:.3f} (per shard) | "
              f"bytes_in_use per device after upload: " + ", ".join(
                  f"{d.id}:{u / 2**20:.3f} MiB"
                  for d, u in zip(mesh.devices.flat, used)))
        print(f"[{label}] compile_count={int(snap['compile_count'])} "
              f"compile_ms_total={snap['compile_ms_total']:.1f}; smoke "
              f"timing, not a benchmark: checks answered in {dt:.3f} s "
              f"wall", flush=True)
        if n_fn:
            _fail(f"{label}: {n_fn} false negatives")
        answers[label] = np.concatenate(mem + non)
    a, b = answers.values()
    print(f"sharded vs local: {int((a != b).sum())} of {len(a)} rows "
          f"disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-sharded path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".smoke_out"),
                    help="directory for the checkpoints")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no src/repro next to {os.path.abspath(__file__)}: run "
              "the script from a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        _fail(f"JAX found no TPU (default backend {backend!r})")
    devices = jax.devices()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, JAX "
              f"sees {len(devices)}")

    t0 = time.perf_counter()
    bases = fit_bases(args.seed)
    ckpt = save_bases(bases, args.out, COPIES)
    queries = make_queries(bases, args.seed, COPIES)
    if args.chips == 4:
        run_mesh(bases, ckpt, queries)
    else:
        run_default(bases, ckpt, queries)
    print(f"total wall {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
