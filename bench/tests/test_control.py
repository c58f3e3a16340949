"""The control: the reference in the program's place, one precision
down, fails the comparison that decides ``correct``.

At a size a CPU test run can hold (the small relation of ``tiny``, a
65 536-row pool, a 5 s schedule): the hidden GEMM in one bfloat16 pass
with float32 accumulation (what the chip does to float32 at default
precision) gives false negatives and mismatched rows; float32 gives
none. On the chip the same comparison runs at the cell's own size
(``bench/control.py``; readings in PERF.md).
"""
import pytest

from bench.control import control_numbers
from bench.tests import tiny


@pytest.fixture(scope="module")
def numbers():
    return control_numbers(tiny.config(), dict(tiny.mix(), pool_rows=65536),
                           tiny.SEED, 5.0, ["bf16", "highest"])


def test_bfloat16_control_is_not_correct(numbers):
    bf16 = numbers["bf16"]
    assert bf16["correct"] is False
    assert bf16["false_negatives"] > 0 and bf16["mismatched_rows"] > 0


def test_float32_reference_in_place_is_correct(numbers):
    f32 = numbers["highest"]
    assert f32["correct"] is True
    assert f32["max_logit_error"] < 1e-5
