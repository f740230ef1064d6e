"""The whole step's share of the chips' peak: the operations the model's
forward and backward require over the window's steps (its ``step_work``,
real rows and edges, no padding or recompute), over the traced window's
seconds times chips times the peak FLOP/s."""

from chipbench import work


def read(ctx):
    if not ctx["peaks"]:
        return None
    w = work.window_total(ctx["window"]["batches"],
                          lambda b: ctx["model"].step_work(b, ctx["cfg"]))
    peak = ctx["peaks"]["flops_per_s"] * ctx["chips"]
    return 100.0 * w["model_ops"] / (ctx["window"]["seconds"] * peak)
