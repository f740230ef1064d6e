"""The ELL kernel's share of its roofline: the least time the window's
required aggregation work could take on the cell's chips (the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, counted
from real edges and rows by the model's ``step_work``) over the kernel's
device time, both summed over the chips."""

from chipbench import trace, work

#: the kernel's operations in the device trace (as ``ell_kernel_ms``)
KERNEL = r"spmm_ell"


def read(ctx):
    if not ctx["trace"] or not ctx["trace"]["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    ops = list(ctx["trace"]["devices"].values())
    kernel_s = sum(trace.op_ns(o, KERNEL, lo, hi) for o in ops) / 1e9
    if not kernel_s:
        return None
    w = work.window_total(ctx["window"]["batches"],
                          lambda b: ctx["model"].step_work(b, ctx["cfg"]))
    peak = ctx["peaks"]
    least = max(w["ell_ops"] / peak["flops_per_s"],
                w["ell_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
