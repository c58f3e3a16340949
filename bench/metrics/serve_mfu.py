"""The whole step's share of the chip's peak: MLP FLOPs per row times
the rows answered in the window, over the window times the peak bf16
FLOP/s, in %. It bounds any gain once a later program replaces the
grouped one."""


def read(ctx):
    if not ctx["rows"] or not ctx["window_s"]:
        return None
    return 100.0 * ctx["work"]["flops"] * ctx["rows"] / (
        ctx["window_s"] * ctx["peaks"]["flops_per_s"])
