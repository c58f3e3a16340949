"""Public jit'd wrappers: arbitrary-rank ids, model-layer integration."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import lmbf
from repro.kernels.qr_embed.q4_gather import q4_gather_call
from repro.kernels.qr_embed.q8_gather import q8_gather_call
from repro.kernels.qr_embed.q_dense import q4_dense_call
from repro.kernels.qr_embed.qr_embed import qr_embed_call


def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def qr_embed(ids, table_q, table_r, *, divisor: int, block_n: int = 1024,
             interpret: Optional[bool] = None):
    """ids: (...,) int32 -> (..., d) compressed-embedding lookup.

    Equivalent to ``table_q[ids // divisor] + table_r[ids % divisor]``
    with the tables VMEM-pinned and the gather executed as one-hot MXU
    matmuls (see qr_embed.py).
    """
    if interpret is None:
        interpret = default_interpret()
    shape = ids.shape
    flat = ids.reshape(-1)
    out = qr_embed_call(flat, table_q, table_r, divisor=divisor,
                        block_n=block_n, interpret=interpret)
    return out.reshape(*shape, table_q.shape[1])


def q8_embed_lookup(idx, sidx, table, scales, *, block_n: int = 1024,
                    interpret: Optional[bool] = None):
    """idx, sidx: (...,) int32 -> (..., d) fused int8 gather + dequant.

    Equivalent to ``table[idx].astype(f32) * scales[sidx][..., None]``
    with the int8 table VMEM-pinned and the scales applied in-tile (see
    q8_gather.py).  Indices must be pre-clipped in-bounds — the caller
    owns wrap/NaN out-of-bounds semantics.
    """
    if interpret is None:
        interpret = default_interpret()
    shape = idx.shape
    out = q8_gather_call(idx.reshape(-1), sidx.reshape(-1), table, scales,
                         block_n=block_n, interpret=interpret)
    return out.reshape(*shape, table.shape[1])


def q4_embed_lookup(idx, sidx, table, scales, *, grid: str = "linear",
                    block_n: int = 1024,
                    interpret: Optional[bool] = None):
    """idx, sidx: (...,) int32 -> (..., 2*pk) fused packed-int4 gather +
    in-tile nibble unpack + LUT dequant.

    Equivalent to ``nibble_values(unpack(table[idx]), grid) *
    scales[sidx][..., None]`` with the packed table VMEM-pinned (see
    q4_gather.py).  Indices must be pre-clipped in-bounds — the caller
    owns wrap/NaN out-of-bounds semantics and trims any odd-width pad
    column.
    """
    if interpret is None:
        interpret = default_interpret()
    shape = idx.shape
    lut = jnp.asarray(lmbf.nibble_lut(grid, scales.dtype))
    out = q4_gather_call(idx.reshape(-1), sidx.reshape(-1), table, scales,
                         lut, block_n=block_n, interpret=interpret)
    return out.reshape(*shape, 2 * table.shape[1])


def q4_dense_dequant(qw, scales, *, prev: int, grid: str = "linear",
                     interpret: Optional[bool] = None):
    """qw: (g, pk, width) packed uint8 dense tiles -> (g, prev, width)
    fp32, nibbles split + LUT-decoded + channel-scaled in-tile (see
    q_dense.py)."""
    if interpret is None:
        interpret = default_interpret()
    lut = jnp.asarray(lmbf.nibble_lut(grid, scales.dtype))
    return q4_dense_call(qw, scales, lut, prev=prev, interpret=interpret)
