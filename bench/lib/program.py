"""The benchmark's only contact with the system under test.

The served path is ``FilterServer.submit_many`` -> ``QueryScheduler.step``
-> ``PlanGroupArena.run`` -> the grouped program -> ``QueryFuture``. This
module hands the program the filters the benchmark made (as checkpoints,
the way a deployment hydrates tenants), builds the server the
configuration pins, and warms its programs. Engine knobs the
configuration does not pin (buckets, dispatch, tile rows) stay at the
program's defaults.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.lib import filters, quant


def save_filter(directory: str, filt: filters.Filter,
                qm: Optional[Dict] = None) -> None:
    """Write ``filt`` as an ``existence_index_v2`` checkpoint, or, under
    the quantization ``qm`` (``quant.mode``), as ``existence_index_v3``
    holding the benchmark's own codes, scales and ``tau_q``."""
    from repro.core import bloom, compression, existence, fixup, lmbf
    cplan = compression.make_plan([c.v for c in filt.cols],
                                  theta=filt.theta, ns=filt.ns)
    cfg = lmbf.LMBFConfig(plan=cplan, hidden=(filt.hidden,))
    idx = existence.ExistenceIndex(
        cfg=cfg, params=filt.params,
        fixup_filter=fixup.FixupFilter(
            params=bloom.BloomParams(m_bits=filt.m_bits,
                                     n_hashes=filt.n_hashes),
            bits=filt.bits, n_false_negatives=filt.n_keys),
        tau=filt.tau, train_log={"made_by": "bench"})
    if qm is None:
        existence.save_index(directory, idx, step=0)
        return
    # the program writes the quantized state it holds for this mode, so
    # hand it ours; had it made its own, the check would test nothing
    idx.quant_cache = {"meta": dict(qm), "qparams": filt.qparams,
                       "tau": filt.tau_q, "pinned": False}
    existence.save_index(directory, idx, step=0, quant=qm)
    if idx.quant_cache["qparams"] is not filt.qparams:
        raise RuntimeError("the program quantized the filter itself: its "
                           f"mode {idx.quant_cache['meta']} is not {qm}")


def serve_config(config: Dict, *, trace: bool):
    """The ``ServeConfig`` the configuration pins: grouping on, float32
    or quantized arenas (``serving.dtype``, ``serving.quant``);
    everything else at the program's defaults. ``trace`` attaches the
    program's span tracer (per-layer metrics only)."""
    from repro.serve_filter import ServeConfig
    from repro.serve_filter.config import GroupingConfig, MetricsConfig
    from repro.serve_filter.plan import QuantConfig
    if not config["serving"]["grouped"]:
        raise ValueError("this harness serves grouped arenas")
    qm = quant.mode(config)
    q = QuantConfig() if qm is None else QuantConfig(enabled=True, **qm)
    return ServeConfig(grouping=GroupingConfig(enabled=True), quant=q,
                       metrics=MetricsConfig(trace=trace))


def admit(server, ckpt_root: str, tenants: List[str]) -> None:
    from repro.serve_filter import TenantSpec
    for t in tenants:
        server.admit(TenantSpec(t, checkpoint=ckpt_root))


def upload(server) -> None:
    """Build every arena's device views now (they are otherwise made at
    the first dispatch)."""
    import jax
    for arena in server.registry.groups.values():
        jax.block_until_ready(arena.device_arrays())


def warm(server, tenant: str, rows: np.ndarray) -> None:
    """Compile (or load from the cache) every bucket's programs: one
    request per bucket, each filling it exactly."""
    for b in server.scheduler.buckets:
        futs = server.submit_many([(tenant, rows[:b])])
        server.run_until_drained()
        futs[0].result()


def compile_count() -> int:
    from repro.serve_filter import executors
    return executors.compile_count()


def serve_totals(server) -> Dict[str, int]:
    t = server.stats.totals
    return {"valid_rows": int(t.queries), "padded_rows": int(t.padded_rows),
            "batches": int(t.batches)}


def program_spans(server, t0: float, t1: float):
    """The program's own host spans (``prepare``, ``dispatch``,
    ``device_block``, ``scatter_retire``) clipped to ``[t0, t1]`` on
    ``time.perf_counter``, and how many the ring buffer dropped."""
    tr = server.tracer
    spans = [(s.name, max(s.t_start, t0), min(s.t_end, t1))
             for s in tr.events()
             if s.cat == "serve" and s.t_end > t0 and s.t_start < t1]
    return spans, tr.dropped


def clear_spans(server) -> None:
    server.tracer.clear()
