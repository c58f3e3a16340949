"""The peaks table and the work counts, against counts worked by hand.

airplane-t5500: columns 6887, 8021, 8046 and 6537 exceed theta 5500 and
split in two (divisors 83, 90, 90, 81; tables of 84+84, 91+91, 91+91,
82+82 rows, 3 wide each), 2557, 5017 and 1663 stay whole (7, 8 and 6
wide): concat_dim 8*3 + 7 + 8 + 6 = 45, input dim 9933 (Table 1).
FLOPs 2*45*64 + 2*64 = 5888; bytes 4*7 ids + 4*45 embedding + 4*7
probe words (7 hashes at FPR 0.01) + 3 answers = 239.

airplane-t5500 served from int4-NF4 arenas: the same 45 embedding
values at half a byte (22.5), plus one 4-byte scale for each of the 11
subcolumn tables a row reads (44): 4*7 + 22.5 + 44 + 4*7 + 3 = 125.5
bytes; in int8, 45 + 44 + 28 + 28 + 3 = 148. The FLOPs do not change.

dmv-t100: ten columns above 100 split (widths 3+3, 2+2, 2+2, 1+1, 2+2,
2+2, 2+2, 2+2, 2+2, 1+1 = 38), nine stay whole (5, 27, 27, 64, 40, 8,
3, 3, 2 rows: 1+2+2+2+2+1+1+1+1 = 13): concat_dim 51, input dim 892.
FLOPs 2*51*64 + 2*64 = 6656; bytes 4*19 + 4*51 + 4*7 + 3 = 311.
"""
import json
import os

import pytest

from bench.lib import filters, work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,concat,input_dim,flops,nbytes", [
    ("airplane-t5500", 45, 9933, 5888.0, 239.0),
    ("dmv-t100", 51, 892, 6656.0, 311.0),
])
def test_counts_by_hand(name, concat, input_dim, flops, nbytes):
    cfg = _config(name)
    cols = filters.plan(cfg["relation"]["cards"], cfg["model"]["theta"],
                        cfg["model"]["ns"])
    assert filters.concat_dim(cols) == concat
    assert sum(filters.table_rows(cols)) == input_dim
    assert work.per_row(cfg) == {"flops": flops, "bytes": nbytes}


@pytest.mark.parametrize("dtype,nbytes", [("int4-nf4", 125.5),
                                          ("int8", 148.0)])
def test_quantized_counts_by_hand(dtype, nbytes):
    cfg = _config("airplane-t5500")
    cfg["serving"] = dict(cfg["serving"], dtype=dtype)
    assert work.per_row(cfg) == {"flops": 5888.0, "bytes": nbytes}


def test_airplane_split_by_hand():
    cols = filters.plan(_config("airplane-t5500")["relation"]["cards"],
                        5500, 2)
    assert [c.divisors for c in cols] == [(83,), (90,), (90,), (81,), (),
                                          (), ()]
    assert filters.embed_dims(cols) == [3] * 8 + [7, 8, 6]


def test_plan_agrees_with_the_program():
    from repro.core import compression, lmbf
    for name in ("airplane-t5500", "dmv-t100"):
        cfg = _config(name)
        m = cfg["model"]
        cols = filters.plan(cfg["relation"]["cards"], m["theta"], m["ns"])
        prog = lmbf.LMBFConfig(plan=compression.make_plan(
            cfg["relation"]["cards"], theta=m["theta"], ns=m["ns"]))
        assert filters.table_rows(cols) == list(prog.plan.table_rows)
        assert filters.concat_dim(cols) == prog.concat_dim


def test_peaks_of_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
