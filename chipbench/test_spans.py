"""The reading of the program's own spans and scopes
(``chipbench/spans.py``) and the per-layer metrics built on it: hand-made
contexts, an ``XSpace`` serialized from text by JAX's own profiler library,
and a small trace recorded on a TPU v5e."""
import gzip
import os

import pytest

from chipbench import run, spans, trace

HOST_METRICS = ("host_sample_ms", "host_gather_ms", "host_layout_ms",
                "host_place_ms", "h2d_mib_per_batch")
DEVICE_METRICS = ("combination_ms", "mirror_backward_ms")


def test_in_scope_reads_segments_inside_wrappers():
    assert spans.in_scope("jit(step)/jvp(combine)/dot_general:", "combine")
    assert spans.in_scope("jit(step)/transpose(jvp(combine))/dot_general",
                          "combine")
    assert spans.in_scope("transpose(jvp(mirror_backward))/all_gather",
                          "mirror_backward")
    assert spans.in_scope("jit(step)/shard_map/mirror_backward/pad",
                          "mirror_backward")
    assert not spans.in_scope("jit(step)/jvp(combined)/dot_general",
                              "combine")
    assert not spans.in_scope("jit(step)/jvp(jit(spmm_ell))/pallas_call",
                              "mirror_backward")
    assert not spans.in_scope("", "combine")


# two devices, a window [0, 100] of 2 steps; a span or an op half outside
# the window counts only if it starts inside (spans) or for its part
# inside (ops)
DEVICES = {
    "/device:TPU:0": [("%fusion.1", 0, 10), ("%kernel.2", 10, 40),
                      ("%t_kernel.3", 40, 70), ("%gather.4", 95, 110)],
    "/device:TPU:1": [("%fusion.1", 0, 6), ("%t_kernel.3", 40, 60)],
}
PATHS = {"%fusion.1": "jit(step)/jvp(combine)/dot_general:",
         "%kernel.2": "jit(step)/jvp(jit(spmm_ell))/pallas_call:",
         "%t_kernel.3": "jit(step)/transpose(jvp(mirror_backward))/"
                        "pallas_call:",
         "%gather.4": "jit(step)/transpose(jvp(mirror_backward))/"
                      "all-gather:"}
HOST = [(1, "pipeline.sample", -30, -5, {"batch": 3}),
        (1, "pipeline.sample", 2, 12, {"batch": 4}),
        (1, "pipeline.gather", 12, 14, {"batch": 4}),
        (1, "engine.layout", 14, 44, {"batch": 4}),
        (1, "engine.place", 44, 50, {"batch": 4, "bytes": 3 * 2**20}),
        (1, "pipeline.sample", 50, 70, {"batch": 5}),
        (1, "pipeline.gather", 70, 76, {"batch": 5}),
        (1, "engine.layout", 76, 86, {"batch": 5}),
        (1, "engine.place", 86, 90, {"batch": 5, "bytes": 2**20}),
        (1, "pipeline.sample", 100, 130, {"batch": 6}),
        (0, "bench.step", 0, 100, {})]


def context(program=None):
    return {"trace": {"devices": DEVICES, "host": []},
            "trace_window": (0, 100), "steps": 2,
            "program": {"host": HOST,
                        "tf_op": {d: PATHS for d in DEVICES}}
            if program is None else program}


def test_host_metrics_average_the_window_spans():
    ctx = context()
    ms = {m: run.read_metric(m, ctx) for m in HOST_METRICS}
    # the spans starting in [0, 100): batches 4 and 5
    assert ms["host_sample_ms"] == pytest.approx((10 + 20) / 2 / 1e6)
    assert ms["host_gather_ms"] == pytest.approx((2 + 6) / 2 / 1e6)
    assert ms["host_layout_ms"] == pytest.approx((30 + 10) / 2 / 1e6)
    assert ms["host_place_ms"] == pytest.approx((6 + 4) / 2 / 1e6)
    assert ms["h2d_mib_per_batch"] == pytest.approx(2.0)


def test_device_metrics_sum_the_scoped_ops_per_chip_and_step():
    ctx = context()
    # combine: 10 ns on chip 0, 6 on chip 1; over 2 chips and 2 steps
    assert run.read_metric("combination_ms", ctx) == pytest.approx(
        (10 + 6) / 2 / 2 / 1e6)
    # mirror backward: 30 + 5 (the gather clipped at 100) and 20
    assert run.read_metric("mirror_backward_ms", ctx) == pytest.approx(
        (35 + 20) / 2 / 2 / 1e6)


def test_metrics_read_nothing_without_the_program_names():
    """A program without the spans and scopes (the parent of the change
    that added them) reads None on every new metric, and raises nothing."""
    bare = context({"host": [(0, "bench.step", 0, 100, {})],
                    "tf_op": {d: {} for d in DEVICES}})
    for m in HOST_METRICS + DEVICE_METRICS:
        assert run.read_metric(m, bare) is None, m
    untraced = {"trace": None}
    for m in HOST_METRICS + DEVICE_METRICS:
        assert run.read_metric(m, untraced) is None, m


XSPACE_TEXT = """
planes {
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 9000 }
    events { metadata_id: 3 offset_ps: 14000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.40 = f32[8] fusion()"
    display_name: "fusion.40"
    stats { metadata_id: 7 str_value: "jit(step)/jvp(combine)/dot_general:" }
    stats { metadata_id: 8 int64_value: 3 } } }
  event_metadata { key: 2 value { id: 2 name: "%ag.1 = f32[8] all-gather()"
    stats { metadata_id: 8 int64_value: 4 }
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f32[8] copy()" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(step)/transpose(jvp(mirror_backward))/all-gather:" } }
}
planes {
  name: "/host:CPU"
  lines { id: 11 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 3000
      stats { metadata_id: 2 int64_value: 4 } } }
  lines { id: 12 name: "python" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 4000
      stats { metadata_id: 2 int64_value: 4 }
      stats { metadata_id: 4 int64_value: 1234 } } }
  event_metadata { key: 1 value { id: 1 name: "pipeline.sample" } }
  event_metadata { key: 3 value { id: 3 name: "engine.place" } }
  stat_metadata { key: 2 value { id: 2 name: "batch" } }
  stat_metadata { key: 4 value { id: 4 name: "bytes" } }
}
"""


@pytest.fixture(scope="module")
def serialized():
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        XSPACE_TEXT)


def test_tf_ops_decodes_string_and_reference_stats(serialized):
    assert spans.tf_ops(serialized) == {"/device:TPU:0": {
        "%fusion.40 = f32[8] fusion()": "jit(step)/jvp(combine)/dot_general:",
        "%ag.1 = f32[8] all-gather()":
            "jit(step)/transpose(jvp(mirror_backward))/all-gather:"}}


def test_load_joins_host_stats_and_device_paths(serialized, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(serialized)
    prog = spans.load(str(path))
    assert [(t, n, st) for t, n, _, _, st in prog["host"]] == [
        (0, "pipeline.sample", {"batch": 4}),
        (1, "engine.place", {"batch": 4, "bytes": 1234})]
    tr = trace.load(str(path))
    # the trace's op names are the metadata's names: the join's key
    names = {o[0] for o in tr["devices"]["/device:TPU:0"]}
    assert set(prog["tf_op"]["/device:TPU:0"]) < names


#: two window steps of ``gcn-flickr.train-x4`` traced on a TPU v5e 2x2 with
#: the program's spans and scopes: every operation of the device planes'
#: ops lines in them, with its name and ``tf_op`` path, and the host's
#: ``bench.*``, ``pipeline.*`` and ``engine.*`` spans with their arguments
RECORDED = os.path.join(os.path.dirname(__file__), "testdata",
                        "v5e_x4_scoped.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    import jax

    with gzip.open(RECORDED, "rb") as f:
        raw = f.read()
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    tr = trace.from_profile(pd)
    steps = [(a, b) for _, n, a, b in tr["host"] if n == "bench.step"]
    return {"trace": tr, "steps": len(steps),
            "trace_window": (min(a for a, _ in steps),
                             max(b for _, b in steps)),
            "program": {"host": spans.host_spans(pd),
                        "tf_op": spans.tf_ops(raw)}}


def test_recorded_trace_scopes(recorded):
    tr, paths = recorded["trace"], recorded["program"]["tf_op"]
    assert sorted(paths) == sorted(tr["devices"]) == [
        f"/device:TPU:{i}" for i in range(4)]
    for plane, ops in tr["devices"].items():
        walks = {n: paths[plane][n] for n, _, _ in ops
                 if "jit(spmm_ell)" in paths[plane].get(n, "")}
        assert walks
        # the transposed walks, and only they, run in the mirror backward;
        # the kernel's ops keep the name ``ell_kernel_ms`` looks for
        for name, path in walks.items():
            assert "spmm_ell" in name
            assert spans.in_scope(path, "mirror_backward") == (
                "transpose" in path), path
        gathers = [paths[plane].get(n, "") for n, _, _ in ops
                   if "all-gather" in n]
        assert gathers and all(spans.in_scope(p, "mirror_backward")
                               for p in gathers)
    # as read from the full trace over the same two steps
    assert run.read_metric("combination_ms", recorded) == pytest.approx(
        0.258097875)
    mirror = run.read_metric("mirror_backward_ms", recorded)
    assert mirror == pytest.approx(27.7051825)
    lo, hi = recorded["trace_window"]
    ops = list(tr["devices"].values())
    per_step = 1e6 * recorded["steps"] * len(ops)
    walk_t = sum(trace.op_ns([o for o in d if "transpose" in
                              paths[p].get(o[0], "")], "spmm_ell", lo, hi)
                 for p, d in tr["devices"].items()) / per_step
    kernel = sum(trace.op_ns(d, "spmm_ell", lo, hi) for d in ops) / per_step
    busy = sum(trace.busy_ns(d, lo, hi) for d in ops) / per_step
    assert 0 < walk_t <= mirror
    assert kernel + run.read_metric("combination_ms", recorded) <= busy


def test_recorded_trace_host_spans(recorded):
    ms = {m: run.read_metric(m, recorded) for m in HOST_METRICS}
    assert ms == pytest.approx({
        "host_sample_ms": 29.186278, "host_gather_ms": 113.88136,
        "host_layout_ms": 80.8324435, "host_place_ms": 40.158792,
        "h2d_mib_per_batch": (106589600 + 106557952) / 2 / 2**20})
    producer = {t for t, n, _, _, _ in recorded["program"]["host"]
                if n in ("pipeline.sample", "engine.place")}
    steps = {t for t, n, _, _, _ in recorded["program"]["host"]
             if n == "bench.step"}
    assert len(producer) == 1 and not producer & steps
