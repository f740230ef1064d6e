"""The trace reduction of ``chipbench/trace.py``: hand-made intervals, and a
small trace recorded on a TPU v5e."""
import gzip
import os

import pytest

from chipbench import trace

# one device: a kernel, a collective half hidden under a fusion, a gap
OPS = [("spmm_ell.1", 0, 10), ("fusion.3", 12, 20),
       ("collective-permute-done.2", 16, 26), ("spmm_ell.4", 30, 35)]
HOST = [("main", "bench.step", 0, 40), ("producer", "bench.prepare", 26, 29),
        ("producer", "bench.sample", 20, 27)]


def test_union_and_busy():
    assert trace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    # busy: [0,10] + [12,26] + [30,35] = 10 + 14 + 5
    assert trace.busy_ns(OPS, 0, 40) == 29
    # clipped to the window [5, 32]: 5 + 14 + 2
    assert trace.busy_ns(OPS, 5, 32) == 21


def test_op_and_exposed_time():
    assert trace.op_ns(OPS, "spmm_ell", 0, 40) == 15
    # the collective runs [16, 26]; the fusion covers [16, 20]
    assert trace.exposed_ns(OPS, "collective-permute", 0, 40) == 6
    assert trace.exposed_ns(OPS, "collective-permute", 0, 18) == 0


def test_breakdown_names_the_gaps():
    tr = {"devices": {"/device:TPU:0": OPS}, "host": HOST}
    out = trace.breakdown(tr, 0, 40, skip_host="^bench.step")
    assert out["device_ops"][0] == ["spmm_ell", 15 / 1e9]
    # gaps [35, 40], [26, 30], [10, 12], longest first; [26, 30] overlaps
    # prepare for 3 ns and sample for 1 ns
    assert out["idle_gaps"] == [["no host span", 5 / 1e9],
                                ["bench.prepare", 4 / 1e9],
                                ["no host span", 2 / 1e9]]


#: two seconds of ``gcn-flickr.train-x4`` traced on a TPU v5e 2x2, cut
#: to the device planes' operation lines and the host's ``bench.*`` spans
RECORDED = os.path.join(os.path.dirname(__file__), "testdata",
                        "v5e_x4_step.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    import jax

    if not os.path.exists(RECORDED):
        pytest.fail(f"missing {RECORDED}")
    with gzip.open(RECORDED, "rb") as f:
        pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return trace.from_profile(pd)


def _sweep_busy(ops, lo, hi):
    """Busy time by a sweep over start (+1) and end (-1) marks: a second
    way to the union, to check :func:`trace.busy_ns` against."""
    import numpy as np

    a = np.clip([o[1] for o in ops], lo, hi)
    b = np.clip([o[2] for o in ops], lo, hi)
    t = np.concatenate([a, b])
    d = np.concatenate([np.ones(len(a)), -np.ones(len(b))])
    order = np.lexsort((-d, t))
    t, depth = t[order], np.cumsum(d[order])
    return float(np.sum(np.diff(t)[depth[:-1] > 0]))


def test_recorded_trace(recorded):
    devs = recorded["devices"]
    assert sorted(devs) == [f"/device:TPU:{i}" for i in range(4)]
    spans = [(a, b) for _, n, a, b in recorded["host"] if n == "bench.step"]
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    for ops in devs.values():
        busy = trace.busy_ns(ops, lo, hi)
        assert busy == pytest.approx(_sweep_busy(ops, lo, hi))
        assert 0 < busy <= hi - lo
        kernel = trace.op_ns(ops, "spmm_ell", lo, hi)
        assert 0 < kernel <= busy
        permute = trace.op_ns(ops, "collective-permute|all-gather", lo, hi)
        exposed = trace.exposed_ns(ops, "collective-permute|all-gather",
                                   lo, hi)
        assert 0 < permute and 0 <= exposed <= permute
    # as read from the full trace before it was cut
    dev0 = devs["/device:TPU:0"]
    assert trace.busy_ns(dev0, lo, hi) == 427044175
    assert trace.op_ns(dev0, "spmm_ell", lo, hi) == 420004343
    assert trace.exposed_ns(dev0, "collective-permute|all-gather",
                            lo, hi) == 1886715
    out = trace.breakdown(recorded, lo, hi, skip_host="^bench.step")
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["device_ops"][0][0] == "transpose_jvp_jit_spmm_ell___"
    assert all(a[1] >= b[1] for a, b in zip(out["device_ops"],
                                            out["device_ops"][1:]))
    assert {g[0] for g in out["idle_gaps"]} <= {"bench.prepare",
                                               "bench.sample",
                                               "no host span"}
