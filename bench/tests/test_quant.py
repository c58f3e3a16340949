"""Quantized configurations through the whole harness, on the CPU.

The benchmark quantizes each relation's filter itself (``bench/lib/
quant.py``), hands the program its codes, scales and ``tau_q`` as
``existence_index_v3`` checkpoints, and checks every answer against its
float64 reference on the weights dequantized in float32, at
``logit(tau_q)``. An int8 and an int4-NF4 run read ``correct`` true.
Each fault planted in the program's quantized path reads false:

* ``float32_tau``: the arena serves the float32 ``tau`` in place of
  ``tau_q``;
* ``linear_grid``: 4-bit codes decoded on the linear grid
  (``code - 8``) in place of NF4;
* ``nibble_swap``: the two nibbles of each byte swapped as they are
  unpacked.

The decode faults are traced into fresh programs: the test empties the
program's cache of grouped executors for its duration.
"""
import numpy as np
import pytest

from bench.control import control_numbers
from bench.lib import filters, quant
from bench.tests import tiny


@pytest.mark.parametrize("dtype", ["int8", "int4-nf4"])
def test_quantized_run_is_correct(dtype):
    out = tiny.measure(tiny.config(dtype=dtype), tiny.mix())
    assert out["correct"] is True
    assert all(v["value"] <= v["limit"] for v in out["checks"].values())
    assert out["failed"] == 0 and out["attempted"] > 0


def _float32_tau(monkeypatch):
    from repro.serve_filter import arena
    quantize_index = arena.quantize_index

    def served_at_tau(index, q):
        qp, _ = quantize_index(index, q)
        return qp, index.tau
    monkeypatch.setattr(arena, "quantize_index", served_at_tau)


def _linear_grid(monkeypatch):
    from repro.core import lmbf
    nibble_values = lmbf.nibble_values
    monkeypatch.setattr(lmbf, "nibble_values",
                        lambda codes, grid, dtype: nibble_values(
                            codes, "linear", dtype))


def _nibble_swap(monkeypatch):
    import jax.numpy as jnp
    from repro.core import lmbf
    unpack_nibbles = lmbf.unpack_nibbles
    monkeypatch.setattr(lmbf, "unpack_nibbles", lambda p, axis: unpack_nibbles(
        (p >> jnp.uint8(4)) | (p << jnp.uint8(4)), axis))


@pytest.mark.parametrize("dtype,fault", [
    ("int8", _float32_tau), ("int4-nf4", _float32_tau),
    ("int4-nf4", _linear_grid), ("int4-nf4", _nibble_swap)])
def test_quantized_fault_reads_incorrect(monkeypatch, dtype, fault):
    from repro.serve_filter import executors
    monkeypatch.setattr(executors, "_GROUPED", {})
    monkeypatch.setattr(executors, "_GREFS", {})
    fault(monkeypatch)
    out = tiny.measure(tiny.config(dtype=dtype), tiny.mix())
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["checks"].values())


def test_float32_tau_control_is_not_correct():
    """The reference in the program's place, thresholding the quantized
    model at the float32 ``tau`` in place of ``tau_q``: false negatives
    (the keys only ``tau_q`` admits are in no fixup filter)."""
    res = control_numbers(tiny.config(dtype="int4-nf4"),
                          dict(tiny.mix(), pool_rows=65536), tiny.SEED, 5.0,
                          ["highest", "tau"])
    assert res["highest"]["correct"] is True
    assert res["tau"]["correct"] is False
    assert res["tau"]["false_negatives"] > 0


@pytest.mark.parametrize("dtype", ["int8", "int4-nf4"])
def test_quantizer_round_trip(dtype):
    """Codes and scales as published: every value within half a step
    of its group's grid, packed nibbles read back in order, and the
    program's own decoding of the benchmark's codes gives the same
    float32 weights."""
    import jax.numpy as jnp
    from repro.core import lmbf
    cfg = tiny.config(dtype=dtype)
    qm = quant.mode(cfg)
    cols = filters.plan(cfg["relation"]["cards"], cfg["model"]["theta"], 2)
    shapes = filters.param_shapes(cols, 8)
    rng = np.random.default_rng(3)
    params = {g: {k: rng.standard_normal(s).astype(np.float32)
                  for k, s in d.items()} for g, d in shapes.items()}
    params["embed"]["col0"][:4] = 0.0        # an all-zero row group
    qp = quant.quantize(params, qm)
    deq = quant.dequantize(qp, shapes, qm)
    assert np.all(qp["embed_scale"]["col0"][0] == 1.0)
    step = {8: 1.0, 4: float(np.diff(quant.NF4).max())}[qm["bits"]]
    for name, t in params["embed"].items():
        per_row = np.repeat(qp["embed_scale"][name], qm["row_group"])
        err = np.abs(deq["embed"][name] - t) / per_row[:len(t), None]
        assert err.max() <= step / 2 + 1e-6
    w = params["dense"]["w0"]
    assert np.abs(deq["dense"]["w0"] - w).max() <= \
        (step / 2 + 1e-6) * qp["dense_scale"]["w0"].max()
    codes = rng.integers(0, 16, (5, 3)).astype(np.uint8)
    assert np.array_equal(quant.unpack(quant.pack(codes, 0), 0, 5), codes)
    assert np.array_equal(quant.unpack(quant.pack(codes, 1), 1, 3), codes)
    prog = lmbf.dequantize_dense(qp, jnp.float32, None if qm["bits"] == 8
                                 else _lmbf_config(cfg),
                                 bits=qm["bits"], grid=qm["grid"])
    for name in ("w0", "w_out"):
        assert np.array_equal(np.asarray(prog[name]), deq["dense"][name])


def _lmbf_config(cfg):
    from repro.core import compression, lmbf
    return lmbf.LMBFConfig(plan=compression.make_plan(
        cfg["relation"]["cards"], theta=cfg["model"]["theta"],
        ns=cfg["model"]["ns"]), hidden=(8,))
