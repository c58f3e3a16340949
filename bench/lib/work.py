"""Peaks of the chips and the work one answered row needs.

The counts are the algorithm's, from the configuration alone, so they
read the same whatever program implements it:

* FLOPs per row: the hidden GEMM (``2 * concat_dim * hidden``) and the
  output dot (``2 * hidden``). Bias adds, ReLU, the sigmoid, the
  division of the ids and the hashing are left out.
* Bytes per row, a lower bound: the row's raw ids (``4 * n_cols``), its
  embedding rows (``4 * concat_dim`` in float32; 1 byte a value in
  int8 and half a byte in int4, plus one 4-byte scale for each of the
  row's subcolumn tables), the fixup bitset words it probes
  (``4 * n_hashes``) and its three answer bytes. The MLP weights are
  left out: a tenant's weights may be read once for many rows. The
  dequantizing multiply is left out of the FLOPs, as bias adds are.

A roofline share is ``max(flops / peak_flops, bytes / peak_bytes)``
over the measured time, so a lower bound of the work never reads above
the truth.
"""
from __future__ import annotations

from typing import Dict

from bench.lib import filters

# the grouped program's compiled modules, by their trace names
PROGRAM_MODULES = ("jit_fused_body", "jit_gather_tiles")

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add "
                       "them to bench/lib/work.py with their source")
    return PEAKS[device_kind]


def program_s(trace: Dict) -> float:
    """Device seconds of the grouped program in a reduced trace."""
    return sum(trace["module_s"].get(m, 0.0) for m in PROGRAM_MODULES)


# bytes of one embedding value as served
VALUE_BYTES = {"float32": 4, "int8": 1, "int4-nf4": 0.5}


def per_row(config: Dict) -> Dict[str, float]:
    """``{"flops", "bytes"}`` one answered row needs under ``config``."""
    m = config["model"]
    cols = filters.plan(config["relation"]["cards"], m["theta"], m["ns"])
    d = filters.concat_dim(cols)
    h = int(m["hidden"][0])
    _, n_hashes = filters.bloom_size(
        int(config["train"]["fixup_capacity"]), config["train"]["fixup_fpr"])
    n_cols = len(config["relation"]["cards"])
    value_bytes = VALUE_BYTES[config["serving"]["dtype"]]
    embed = value_bytes * d
    if value_bytes < 4:
        embed += 4 * len(filters.table_rows(cols))
    return {"flops": float(2 * d * h + 2 * h),
            "bytes": float(4 * n_cols + embed + 4 * n_hashes + 3)}
