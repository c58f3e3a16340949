"""The served grouped programs compile for a TPU v5e, with no chip attached.

Each test lowers ``GroupedExecutor.fn`` and ``gather_tiles`` at bucket
4096 with the shapes of a CPU-built ``PlanGroupArena`` of 32 tenants at
the paper's widths (airplane theta=5500, DMV theta=100), placed on a
described ``v5e:2x2`` topology, and compiles them with the TPU compiler:
whatever the chip's compiler refuses fails here at no chip time. The
topology is described inside a fixture, never at import, so only the
test worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import bloom, compression, existence, fixup, lmbf, memory
from repro.serve_filter.arena import PlanGroupArena
from repro.serve_filter.executors import GroupedExecutor
from repro.serve_filter.plan import QuantConfig, group_key, plan_query

BUCKET = 4096
TENANTS = 32
BASES = {"airplane": (memory.AIRPLANE_CARDS, 5500),
         "dmv": (memory.DMV_CARDS, 100)}
QUANTS = {"fp32": QuantConfig(),
          "int8": QuantConfig(enabled=True, bits=8),
          "int4nf4": QuantConfig(enabled=True, bits=4, grid="nf4")}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _index(base: str) -> existence.ExistenceIndex:
    """An unfitted index at the base's real widths (random weights)."""
    cards, theta = BASES[base]
    cfg = lmbf.LMBFConfig(plan=compression.make_plan(cards, theta=theta),
                          hidden=(64,))
    params = jax.tree.map(np.asarray, lmbf.init(cfg, jax.random.key(0)))
    bp = bloom.params_for(20_000, 0.01)
    fx = fixup.FixupFilter(params=bp, bits=bloom.empty(bp),
                           n_false_negatives=0)
    return existence.ExistenceIndex(cfg=cfg, params=params,
                                    fixup_filter=fx, tau=0.5, train_log={})


def _arena_arrays(base: str, quant: QuantConfig):
    """Host-built arena operands ``(params, bits, tau, m_bits, base)``
    for TENANTS copies of one index under a local group key."""
    idx = _index(base)
    key = group_key(plan_query(idx.cfg, idx.fixup_filter.params,
                               quant=quant))
    arena = PlanGroupArena(key, GroupedExecutor(key))
    for k in range(TENANTS):
        arena.add(f"t{k}", idx)
    return idx, arena.device_arrays()


def _shapes(tree, sharding_of):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding_of(x)), tree)


def _compile_grouped(ex, operands, n_cols: int, sharding_of):
    """Compile gather_tiles and the megabatch program for one bucket."""
    params, bits, tau, m_bits, base = operands
    params = _shapes(params, sharding_of)
    repl = sharding_of(None)
    tile_idx = jax.ShapeDtypeStruct((BUCKET // ex.key.tile_rows,),
                                    jnp.int32, sharding=repl)
    ex.gather_tiles.lower(params, tile_idx).compile()
    tiles = _shapes(jax.eval_shape(ex.gather_tiles, params, tile_idx),
                    lambda _: repl)
    rows = jax.ShapeDtypeStruct((BUCKET,), jnp.int32, sharding=repl)
    raw = jax.ShapeDtypeStruct((BUCKET, n_cols), jnp.int32, sharding=repl)
    compiled = ex.fn.lower(params, tiles, *_shapes(
        (bits, tau, m_bits, base), sharding_of), rows, raw).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("quant", list(QUANTS))
@pytest.mark.parametrize("base", list(BASES))
def test_grouped_program_compiles_for_v5e(topo, base, quant):
    one_chip = SingleDeviceSharding(topo.devices[0])
    idx, operands = _arena_arrays(base, QUANTS[quant])
    key = group_key(plan_query(idx.cfg, idx.fixup_filter.params,
                               quant=QUANTS[quant]))
    _compile_grouped(GroupedExecutor(key), operands,
                     idx.cfg.plan.n_columns, lambda _: one_chip)


def test_sharded_grouped_program_compiles_for_v5e_mesh(topo):
    """Airplane fp32 arena over the four described chips: the combined
    embedding matrix row-sharded, the bitsets word-sharded, one psum."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    idx, (params, bits, tau, m_bits, base) = _arena_arrays(
        "airplane", QUANTS["fp32"])
    key = group_key(plan_query(idx.cfg, idx.fixup_filter.params,
                               mesh=mesh))
    assert key.placement.sharded and key.placement.n_shards == 4

    def pad(x):     # what PlanGroupArena does before sharding a view
        return np.zeros((-(-x.shape[0] // 4) * 4,) + x.shape[1:], x.dtype)

    params = dict(params, embed_flat=pad(params["embed_flat"]))
    split = {id(params["embed_flat"]): P("data", None)}
    bits = pad(bits)
    split[id(bits)] = P("data")
    compiled = _compile_grouped(
        GroupedExecutor(key, mesh), (params, bits, tau, m_bits, base),
        idx.cfg.plan.n_columns,
        lambda x: NamedSharding(mesh, split.get(id(x), P())))
    assert "all-reduce" in compiled.as_text()
