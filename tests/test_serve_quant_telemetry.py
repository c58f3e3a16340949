"""What quantized serving costs, as the program reports it.

* ``tenant_device_mb``: the device bytes of the arrays live entries hold
  outside their arenas (index params, fixup bits, the quant cache,
  ungrouped placements), as the device stores them;
* ``arena_quant_tau_margin_max`` / ``_mean``: how far calibration
  lowered each quantized tenant's threshold, ``logit(tau) -
  logit(tau_q)``, kept per tenant through add, swap and remove; float32
  arenas report none;
* named scopes ``q_gather``, ``dequant`` (``fused_body``) and
  ``dequant_dense`` (``gather_tiles``) in the lowered quantized grouped
  programs, and in no float32 one;
* the ``restore`` and ``quant_state`` spans that split an admission
  from a checkpoint, inside its ``admit`` span.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import existence
from repro.data import tuples
from repro.runtime.trace import Tracer
from repro.serve_filter import FilterServer, ServeConfig, TenantSpec
from repro.serve_filter.config import QuantConfig
from repro.serve_filter.plan import quant_meta

ST = existence.TrainSettings(steps=40, n_pos=1000, n_neg=1000)

QUANTS = {"fp32": QuantConfig(),
          "int8": QuantConfig(enabled=True, bits=8),
          "int4-linear": QuantConfig(enabled=True, bits=4, grid="linear"),
          "int4-nf4": QuantConfig(enabled=True, bits=4, grid="nf4")}
STAGES = ("/q_gather/", "/dequant/", "/dequant_dense/")


@pytest.fixture(scope="module")
def fleet():
    out = {}
    for name, (cards, theta, seed) in {
            "wide": ([3000, 800], 4000, 1),
            "tri": ([400, 250, 90], 150, 3)}.items():
        ds = tuples.synthesize(cards, n_records=600, seed=seed)
        out[name] = existence.fit(ds, theta=theta, settings=ST)
    return out


def _server(quant: QuantConfig, grouped: bool = True, trace: bool = False):
    return FilterServer(ServeConfig.from_kwargs(
        grouped=grouped, quantized=quant.enabled, quant_bits=quant.bits,
        quant_grid=quant.grid, trace=trace))


def _logit(p):
    return math.log(p / (1.0 - p))


def _entry_device_bytes(srv) -> int:
    """The same count, written out: every distinct JAX array an entry
    holds outside its arena. The CPU pads nothing, so the device
    stores each in its ``nbytes``."""
    arrays = {}
    for t in srv.registry.tenants:
        e = srv.registry.peek(t)
        trees = [e.index.params, e.index.fixup_filter.bits]
        if e.index.quant_cache is not None:
            trees.append(e.index.quant_cache["qparams"])
        if e.placed is not None:
            trees += [e.placed.params, e.placed.bits]
        for leaf in jax.tree.leaves(trees):
            if isinstance(leaf, jax.Array):
                arrays[id(leaf)] = leaf
    return sum(a.nbytes for a in arrays.values())


# ------------------------------------------------------ tenant_device_mb

@pytest.mark.parametrize("mode,grouped", [
    ("fp32", True), ("int4-nf4", True), ("fp32", False),
    ("int8", False)])
def test_tenant_device_mb_counts_entry_arrays(fleet, mode, grouped):
    srv = _server(QUANTS[mode], grouped=grouped)
    for name, idx in fleet.items():
        srv.admit(TenantSpec(name, index=idx))
    snap = srv.stats_snapshot()
    want = _entry_device_bytes(srv)
    assert want > 0         # a fitted index keeps its params on device
    assert snap["tenant_device_mb"] == want / 2 ** 20
    srv.evict("wide")
    assert srv.stats_snapshot()["tenant_device_mb"] == \
        _entry_device_bytes(srv) / 2 ** 20 < want / 2 ** 20
    srv.close()


def test_tenant_device_mb_counts_a_shared_index_once(fleet):
    srv = _server(QUANTS["fp32"])
    srv.admit(TenantSpec("a", index=fleet["tri"]))
    one = srv.stats_snapshot()["tenant_device_mb"]
    srv.admit(TenantSpec("b", index=fleet["tri"]))
    assert srv.stats_snapshot()["tenant_device_mb"] == one
    srv.close()


# ------------------------------------------------------ threshold margins

@pytest.mark.parametrize("mode", ["int8", "int4-nf4"])
def test_tau_margin_per_tenant(fleet, mode):
    q = QUANTS[mode]
    srv = _server(q)
    for name, idx in fleet.items():
        srv.admit(TenantSpec(name, index=idx))
    margins = {}
    for name, idx in fleet.items():
        _, tau_q = existence.ensure_quant_state(idx, quant_meta(q))
        arena = srv.registry.peek(name).group
        got = arena.tau_margins()[name]
        assert got == pytest.approx(_logit(idx.tau) - _logit(tau_q),
                                    rel=1e-12)
        assert got > 0
        margins[name] = got
    snap = srv.stats_snapshot()
    assert snap["arena_quant_tau_margin_max"] == max(margins.values())
    assert snap["arena_quant_tau_margin_mean"] == \
        pytest.approx(np.mean(list(margins.values())), rel=1e-12)
    # a hot reload swaps the slot and its margin; an eviction drops it
    srv.admit(TenantSpec("wide", index=fleet["wide"]))
    assert srv.registry.peek("wide").group.tau_margins()["wide"] == \
        margins["wide"]
    srv.evict("wide")
    snap = srv.stats_snapshot()
    assert snap["arena_quant_tau_margin_max"] == margins["tri"]
    assert snap["arena_quant_tau_margin_mean"] == margins["tri"]
    srv.close()


@pytest.mark.parametrize("tau", [0.0, 1.0])
def test_tau_margin_at_an_edge_tau(fleet, tau):
    # calibration clamps tau into (0, 1) before lowering it; the margin
    # reads from the same clamp, so such a tenant still admits
    idx = dataclasses.replace(fleet["tri"], tau=tau, quant_cache=None)
    srv = _server(QUANTS["int8"])
    srv.admit(TenantSpec("t", index=idx))
    _, tau_q = existence.ensure_quant_state(idx, quant_meta(QUANTS["int8"]))
    t = min(max(tau, 1e-6), 1.0 - 1e-6)
    got = srv.stats_snapshot()["arena_quant_tau_margin_max"]
    assert got == pytest.approx(_logit(t) - _logit(tau_q), rel=1e-9)
    assert math.isfinite(got) and got > 0
    srv.close()


def test_float32_arena_reports_no_margin(fleet):
    srv = _server(QUANTS["fp32"])
    for name, idx in fleet.items():
        srv.admit(TenantSpec(name, index=idx))
    for name in fleet:
        assert srv.registry.peek(name).group.tau_margins() == {}
    snap = srv.stats_snapshot()
    assert snap["arena_quant_tau_margin_max"] == 0.0
    assert snap["arena_quant_tau_margin_mean"] == 0.0
    srv.close()


# ------------------------------------------------------------ named scopes

def _lowered(idx, quant: QuantConfig):
    """Debug-info text of the lowered (gather_tiles, fused) programs of
    a local group of two copies of ``idx``."""
    srv = _server(quant)
    srv.admit(TenantSpec("a", index=idx))
    srv.admit(TenantSpec("b", index=idx))
    arena = srv.registry.peek("a").group
    params, bits, tau, m_bits, base = arena.device_arrays()
    ex = arena.executor
    n = 2 * arena.tile_rows
    tile_idx = jnp.zeros(2, jnp.int32)
    tiles = ex.gather_tiles(params, tile_idx)
    texts = (ex.gather_tiles.lower(params, tile_idx),
             ex.fn.lower(params, tiles, bits, tau, m_bits, base,
                         jnp.zeros(n, jnp.int32),
                         jnp.zeros((n, idx.cfg.plan.n_columns), jnp.int32)))
    srv.close()
    return tuple(t.as_text(debug_info=True) for t in texts)


@pytest.mark.parametrize("mode", ["int8", "int4-linear", "int4-nf4"])
def test_quantized_program_names_its_stages(fleet, mode):
    tiles, fused = _lowered(fleet["tri"], QUANTS[mode])
    assert "/dequant_dense/" in tiles
    assert "/q_gather/" in fused and "/dequant/" in fused


def test_float32_program_names_no_quant_stage(fleet):
    tiles, fused = _lowered(fleet["tri"], QUANTS["fp32"])
    for text in (tiles, fused):
        assert not any(s in text for s in STAGES)


# ----------------------------------------------------------- admit spans

@pytest.mark.parametrize("v3", [True, False])
def test_admit_from_checkpoint_splits_restore_and_quant_state(
        fleet, tmp_path, v3):
    q = QUANTS["int4-nf4"]
    idx = fleet["tri"]
    existence.save_index(os.path.join(tmp_path, "t"), idx, step=1,
                         quant=quant_meta(q) if v3 else None)
    srv = _server(q, trace=True)
    srv.admit(TenantSpec("t", checkpoint=str(tmp_path)))
    spans = srv.tracer.events()
    admit = [s for s in spans if s.name == "admit"]
    assert len(admit) == 1
    inside = {s.name: s for s in spans if s.parent == "admit"}
    assert inside["restore"].cat == "detail"
    assert admit[0].t_start <= inside["restore"].t_start
    if v3:
        qs = inside["quant_state"]
        assert qs.cat == "detail"
        assert inside["restore"].t_end <= qs.t_start <= qs.t_end \
            <= admit[0].t_end
        assert srv.registry.peek("t").index.quant_cache["pinned"]
    else:
        # a v2 checkpoint carries no quantized state to bind
        assert "quant_state" not in inside
    srv.close()


def test_load_index_spans_on_its_tracer(fleet, tmp_path):
    existence.save_index(str(tmp_path), fleet["tri"], step=1,
                         quant=quant_meta(QUANTS["int8"]))
    tracer = Tracer()
    got = existence.load_index(str(tmp_path), step=1, tracer=tracer)
    assert [(s.name, s.cat, s.depth) for s in tracer.events()] == [
        ("restore", "detail", 0), ("quant_state", "detail", 0)]
    assert got.quant_cache["pinned"]
