"""The whole step's share of the chips' peak: the operations the GCN's
forward and backward require over the window's steps (``chipbench/work.py``,
real rows and edges, no padding or recompute), over the traced window's
seconds times chips times the peak FLOP/s."""

from chipbench import work


def read(ctx):
    if not ctx["peaks"]:
        return None
    w = work.window_work(ctx["window"]["batches"], ctx["dims_io"])
    peak = ctx["peaks"]["flops_per_s"] * ctx["chips"]
    return 100.0 * w["model_ops"] / (ctx["window"]["seconds"] * peak)
