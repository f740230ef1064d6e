"""Device milliseconds per window step, per chip, in which the hypercube
exchange's collectives (the fold's ``ppermute`` and the backward's
all-gather) run with no other operation beside them."""

from chipbench import trace

#: the exchange's operations in the device trace
EXCHANGE = r"collective-permute|all-gather"


def read(ctx):
    if not ctx["trace"] or len(ctx["trace"]["devices"]) < 2:
        return None
    lo, hi = ctx["trace_window"]
    ops = list(ctx["trace"]["devices"].values())
    ns = sum(trace.exposed_ns(o, EXCHANGE, lo, hi) for o in ops) / len(ops)
    return ns / 1e6 / ctx["steps"]
