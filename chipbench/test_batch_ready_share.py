"""The ``batch_ready_share`` reader on a hand-made context: the window's
``pipeline.handoff`` spans, as ``chipbench/test_spans.py`` builds them for
the other host metrics."""
import pytest

from chipbench import run

# a window [0, 100]; a span counts only if it starts inside it
HOST = [(1, "pipeline.handoff", -8, -6, {"batch": 3, "ready": 0}),
        (1, "engine.place", 44, 50, {"batch": 4, "bytes": 2**20}),
        (1, "pipeline.handoff", 50, 51, {"batch": 4, "ready": 1}),
        (1, "pipeline.handoff", 70, 71, {"batch": 5, "ready": 0}),
        (1, "pipeline.handoff", 90, 92, {"batch": 6, "ready": 1}),
        (1, "pipeline.handoff", 99, 101, {"batch": 7, "ready": 1}),
        (1, "pipeline.handoff", 100, 102, {"batch": 8, "ready": 0}),
        (0, "bench.step", 0, 100, {})]


def context(host):
    return {"trace": {"devices": {}, "host": []},
            "trace_window": (0, 100), "steps": 2,
            "program": {"host": host, "tf_op": {}}}


def test_batch_ready_share_averages_the_window_handoffs():
    # batches 4 to 7 start inside: three of four ready
    assert run.read_metric("batch_ready_share", context(HOST)) \
        == pytest.approx(75.0)


def test_batch_ready_share_reads_nothing_without_handoffs():
    """A program without the hand-off span (the parent of the change that
    added it) reads None, and raises nothing."""
    bare = [s for s in HOST if s[1] != "pipeline.handoff"]
    assert run.read_metric("batch_ready_share", context(bare)) is None
    assert run.read_metric("batch_ready_share", {"trace": None}) is None
