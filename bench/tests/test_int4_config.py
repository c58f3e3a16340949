"""The int4-NF4 airplane configuration is airplane at the published
widths, served from packed NF4 arenas.

It may differ from ``airplane-t5500.json`` only in its name, its
source line (which adds the NF4 code book), ``serving.dtype``,
``serving.quant`` and the text of ``guarantees`` and ``assumed``:
every width, cardinality, tenant count and trainer setting stays as
published, and ``reduced`` stays empty.
"""
import json
import os

from bench import run
from bench.lib import program, spec, work

NAME = "airplane-t5500-int4nf4-rg32"
CELL = "airplane-t5500-int4nf4.bulk-closed"
# the bulk loop's per-layer metrics, which both bulk cells report
BULK_METRICS = (
    "host_busy_share.bulk", "occupancy.bulk", "program_us_per_krow.bulk",
    "program_roofline.bulk", "device_idle_share.bulk", "serve_mfu.bulk",
    "prepare_us_per_krow.bulk", "dispatch_us_per_krow.bulk",
    "block_us_per_krow.bulk", "retire_us_per_krow.bulk")


def _load(name):
    with open(os.path.join(run.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_only_the_serving_dtype_and_its_knobs_differ():
    base, q = _load("airplane-t5500"), _load(NAME)
    assert q["name"] == NAME
    assert set(q) == set(base)
    for key in set(base) - {"name", "source", "serving", "guarantees",
                            "assumed"}:
        assert q[key] == base[key], key
    assert {k: v for k, v in q["serving"].items()
            if k not in ("dtype", "quant")} == \
        {k: v for k, v in base["serving"].items() if k != "dtype"}
    assert q["serving"]["dtype"] == "int4-nf4"
    assert q["serving"]["quant"] == {"row_group": 32, "calib_samples": 512,
                                     "margin_safety": 2.0,
                                     "margin_floor": 0.001}
    assert set(q["guarantees"]) == set(base["guarantees"])
    assert set(base["assumed"]) <= set(q["assumed"])
    assert {"quant", "fixup_union"} <= set(q["assumed"])


def test_knobs_are_the_programs_defaults():
    from repro.serve_filter.config import QuantConfig
    got = program.serve_config(_load(NAME), trace=False).quant
    want = QuantConfig()
    assert (got.enabled, got.bits, got.grid) == (True, 4, "nf4")
    assert (got.row_group, got.calib_samples, got.margin_safety,
            got.margin_floor) == (want.row_group, want.calib_samples,
                                  want.margin_safety, want.margin_floor)


def test_work_per_row():
    assert work.per_row(_load(NAME)) == {"flops": 5888.0, "bytes": 125.5}


def test_entries_name_the_file_and_the_cell():
    bench = spec.load(run.ROOT)
    entry = [c for c in bench["configs"] if c["name"] == NAME]
    assert len(entry) == 1
    assert entry[0]["file"] == f"bench/configs/{NAME}.json"
    assert entry[0]["reduced"] == []
    assert entry[0]["source"] == _load(NAME)["source"]
    assert len(entry[0]["source"]) <= 200
    cell = spec.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "bulk-closed", 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in BULK_METRICS:
        assert CELL in per_layer[name]["workloads"], name
        assert "dmv-t100.bulk-closed" in per_layer[name]["workloads"], name
