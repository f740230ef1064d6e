"""Device milliseconds per window step in the ELL aggregation kernel
(``kernels/spmm.py``, forward and transposed walks), per chip."""

from chipbench import trace

#: the kernel's operations in the device trace
KERNEL = r"spmm_ell"


def read(ctx):
    if not ctx["trace"] or not ctx["trace"]["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    ops = list(ctx["trace"]["devices"].values())
    ns = sum(trace.op_ns(o, KERNEL, lo, hi) for o in ops) / len(ops)
    return ns / 1e6 / ctx["steps"] if ns else None
