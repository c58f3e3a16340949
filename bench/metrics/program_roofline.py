"""The least time the chip could take for the valid rows the grouped
program answered in the traced window, over the device time it took,
in %. The least time is the larger of the rows' FLOPs over the peak
FLOP/s and their bytes over the peak HBM bandwidth
(``bench/lib/work.py``)."""
from bench.lib.work import program_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["serve"]["valid_rows"] or not program_s(tr):
        return None
    rows, w, p = ctx["serve"]["valid_rows"], ctx["work"], ctx["peaks"]
    least = max(rows * w["flops"] / p["flops_per_s"],
                rows * w["bytes"] / p["bytes_per_s"])
    return 100.0 * least / program_s(tr)
