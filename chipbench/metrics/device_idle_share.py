"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""

from chipbench import trace


def read(ctx):
    if not ctx["trace"] or not ctx["trace"]["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    ops = list(ctx["trace"]["devices"].values())
    busy = sum(trace.busy_ns(o, lo, hi) for o in ops) / len(ops)
    return 100.0 * (1.0 - busy / (hi - lo))
