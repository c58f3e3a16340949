"""Pipeline parallelism: shard_map + ppermute microbatch loop.

GPipe-style schedule over a dedicated ``pipe`` mesh axis: the layer
stack is split into ``n_stages`` contiguous groups; microbatches stream
stage-to-stage with ``jax.lax.ppermute``. Forward-only steady-state
utilization is ``M / (M + S - 1)`` for M microbatches on S stages — the
bubble term is reported by :func:`bubble_fraction` and the schedule is
validated numerically against the unpipelined stack in
tests/test_pipeline.py (on a small host mesh, same code path as a
production ``(pipe, data, model)`` mesh).

This is the optional PP axis noted in DESIGN.md: the assigned
production meshes are (data, model) / (pod, data, model), so PP is a
framework feature demonstrated at test scale, not part of the required
dry-run matrix.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stage_split(n_layers: int, n_stages: int):
    """Contiguous [start, stop) layer ranges per stage."""
    per = -(-n_layers // n_stages)
    return [(s * per, min((s + 1) * per, n_layers))
            for s in range(n_stages)]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stack_params, layer_fn: Callable, x, *, mesh: Mesh,
                   axis: str = "pipe", n_micro: int = None):
    """Run a stacked-parameter layer sequence as a pipeline.

    stack_params: pytree with leading dim = n_layers (stacked layers).
    layer_fn(params_slice, x) -> x for ONE layer.
    x: (batch, ...) activations; batch % n_micro == 0.

    Each of the ``n_stages`` = mesh.shape[axis] devices holds its layer
    slice (params sharded on the stacked axis); microbatches are pushed
    through with ppermute. Returns y with x's shape.
    """
    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stack_params)[0].shape[0]
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    per_stage = n_layers // n_stages
    B = x.shape[0]
    n_micro = n_micro or n_stages
    assert B % n_micro == 0
    mb = B // n_micro

    def stage_fn(params_local, x_all):
        """Runs on one device: params_local (1, per_stage, ...) — the
        shard of the (n_stages, per_stage, ...) stack; x_all (B, ...)."""
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)

        def run_stage(carry_x):
            def body(x_in, p_slice):
                return layer_fn(p_slice, x_in), None
            y, _ = jax.lax.scan(
                lambda c, p: (layer_fn(p, c), None), carry_x,
                params_local)
            return y

        # microbatch queue: step t processes microbatch (t - stage) if
        # 0 <= t - stage < n_micro; total steps = n_micro + n_stages - 1
        n_steps = n_micro + n_stages - 1
        # carries become pipe-varying after the first ppermute — mark
        # the initial values varying so the loop carry types match
        out = jax.lax.pcast(jnp.zeros_like(x_all), (axis,), to="varying")
        cur = jax.lax.pcast(
            jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype), (axis,),
            to="varying")

        def step(t, state):
            cur, out = state
            # stage 0 ingests microbatch t (if valid)
            take = jax.lax.dynamic_slice_in_dim(
                x_all, (jnp.clip(t, 0, n_micro - 1)) * mb, mb, 0)
            cur = jnp.where(stage == 0,
                            jnp.where(t < n_micro, take, cur), cur)
            # every stage runs its layers on its current microbatch
            y = run_stage(cur)
            # last stage emits microbatch (t - n_stages + 1)
            emit_idx = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            out = jnp.where(
                emit,
                jax.lax.dynamic_update_slice_in_dim(
                    out, y, emit_idx * mb, 0),
                out)
            # pass activations downstream (stage s -> s+1), ring-wrapped
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages)
                          for i in range(n_stages)])
            return (nxt, out)

        cur, out = jax.lax.fori_loop(0, n_steps, step, (cur, out))
        # only the last stage holds real output; broadcast it
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out

    params_sharded = jax.tree.map(
        lambda p: p.reshape((n_stages, per_stage) + p.shape[1:]),
        stack_params)
    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    # the reshape above runs eagerly on the default device; under the
    # mesh context the shard_map call places its operands on the mesh
    with jax.set_mesh(mesh):
        return fn(params_sharded, x)
