"""The program's profiler spans and named scopes.

The input chain's four stage threads mark each batch's host stages with
``jax.profiler.TraceAnnotation`` spans (``pipeline.sample``,
``pipeline.gather``, ``engine.layout``, ``engine.place``), each tagged with
the pipeline's batch index, the place span also with the bytes it places;
the place thread marks each hand-off to the step loop
(``pipeline.handoff``).
The train step names two device layers with ``jax.named_scope``:
``combine`` (the combination ``h @ W``) and ``mirror_backward`` (the ELL
aggregation's custom-vjp backward).  The chip benchmark's per-layer
metrics read these names (``chipbench/spans.py``)."""
import glob
import json
import re
import textwrap

import jax
import pytest

from conftest import run_subprocess

STAGES = ("pipeline.sample", "pipeline.gather", "engine.layout",
          "engine.place")


def _host_spans(trace_dir):
    """``(thread, name, start_ns, stats)`` of every host event; a thread is
    its line's index, as :class:`jax.profiler.ProfileData` names every
    Python thread's line alike."""
    [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out, thread = [], 0
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(thread, e.name, e.start_ns, dict(e.stats))
                        for e in line.events]
                thread += 1
    return out


def test_producer_spans_one_per_batch(tmp_path):
    from repro.launch.trainer import Trainer

    tr = Trainer("ell+pipelined", "flickr", n_cores=1, scale=0.005,
                 feat_dim=16, hidden=16, batch_size=16, lr=0.2, seed=3,
                 input_pipeline="prefetch", val_batches=1, ckpt_every=0)
    placed = {}
    commit = tr.bundle.commit_batch

    def recording(host_batch):
        out = commit(host_batch)
        placed[host_batch["batch"]] = out
        return out

    tr.bundle.commit_batch = recording
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.consumer"):
            tr.train_steps(3)
        # the producer finishes the batch it started, then stops
        tr.close()
    finally:
        jax.profiler.stop_trace()

    spans = _host_spans(tmp_path)
    by_name = {name: [s for s in spans if s[1] == name] for name in STAGES}
    # every batch placed: the three consumed and at most one ahead
    assert set(range(3)) <= set(placed) and len(placed) <= 3 + 1
    # each stage saw every batch the next one did, and no batch twice;
    # the place spans are exactly the batches placed
    tagged = [[s[3]["batch"] for s in by_name[name]] for name in STAGES]
    for up, down in zip(tagged, tagged[1:]):
        assert len(set(up)) == len(up) and set(down) <= set(up)
    assert sorted(tagged[-1]) == sorted(placed)
    # each stage on a thread of its own, none of them the consumer's
    [consumer] = {s[0] for s in spans if s[1] == "test.consumer"}
    threads = [{s[0] for s in found} for found in by_name.values()]
    assert all(len(t) == 1 for t in threads)
    assert len(set.union(*threads)) == len(STAGES)
    assert consumer not in set.union(*threads)
    for batch, out in placed.items():
        starts = [next(s[2] for s in by_name[name] if s[3]["batch"] == batch)
                  for name in STAGES]
        assert starts == sorted(starts), batch
        [place] = [s for s in by_name["engine.place"]
                   if s[3]["batch"] == batch]
        leaves = jax.tree_util.tree_leaves(
            [out["edges"], out["x"], out["labels"]])
        assert place[3]["bytes"] == sum(a.nbytes for a in leaves) > 0
    # one hand-off per batch placed, on the place thread, after its place
    handoffs = [s for s in spans if s[1] == "pipeline.handoff"]
    assert sorted(s[3]["batch"] for s in handoffs) == sorted(placed)
    assert {s[0] for s in handoffs} == threads[-1]
    for h in handoffs:
        assert h[3]["ready"] in (0, 1)
        [place] = [s for s in by_name["engine.place"]
                   if s[3]["batch"] == h[3]["batch"]]
        assert h[2] >= place[2]

_LOWERED = """
import json, re
from repro.launch.trainer import Trainer

tr = Trainer("ell+pipelined", "flickr", n_cores={n_cores}, scale=0.005,
             feat_dim=16, hidden=16, batch_size=16, lr=0.2, seed=3,
             input_pipeline="sync", val_batches=1, ckpt_every=0)
batch = tr._next_batch()
text = tr.bundle.train_step_fn(batch["dims"]).lower(
    tr.params, batch).as_text(debug_info=True)
print(json.dumps(sorted(set(re.findall(r'loc\\("([^"]*)"', text)))))
"""


@pytest.mark.parametrize("n_cores", [1, 4])
def test_step_carries_the_device_scopes(n_cores):
    out = run_subprocess(textwrap.dedent(_LOWERED.format(n_cores=n_cores)),
                         n_devices=n_cores)
    names = json.loads(out.strip().splitlines()[-1])

    def found(path):
        # inside shard_map (several devices) the path starts at the body
        return [n for n in names if re.search(r"(^|/)" + re.escape(path), n)]

    # the combination forward and its transpose
    assert found("jvp(combine)/dot_general"), names
    assert found("transpose(jvp(combine))/dot_general"), names
    # the mirror backward: the error all-gather and the transposed walk
    assert found("transpose(jvp(mirror_backward))/all_gather"), names
    assert len(found("transpose(jvp(mirror_backward))/")) > 1, names
