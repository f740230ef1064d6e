"""The engine-native Trainer + async input pipeline.

Contracts:
  * the :class:`~repro.data.Prefetcher` preserves the restartable-stream
    contract — ordering, state of the last CONSUMED batch (in-flight work
    excluded), drain-on-close with rewind, producer errors re-raised on
    the consumer;
  * the :class:`~repro.data.BatchChain` places at most one batch ahead of
    the consumer, marks each hand-off ``ready`` or not, and runs a source
    or layout callable assigned after construction;
  * sync and prefetch input pipelines consume bit-identical batch streams
    (same losses, step for step);
  * checkpoint mid-epoch WITH batches in flight + restore replays the
    remaining batch stream and loss trajectory bit-exactly vs an
    uninterrupted run;
  * every registered format×schedule spec trains end-to-end through the
    Trainer on 2 simulated devices, within 1e-4 of the coo+serial oracle
    trajectory (the ISSUE-4 acceptance bar — formats the old train_gcn
    hard-rejected train here via the host-side ``prepare_batch`` hook);
  * multilabel datasets train through the argmax proxy.
"""
import numpy as np
import pytest
import textwrap

from conftest import run_subprocess


# ---------------------------------------------------------------------------
# Prefetcher unit contract (no jax, no devices).
# ---------------------------------------------------------------------------
class _CountSource:
    """Deterministic restartable stream: yields (idx,) tuples."""

    def __init__(self, idx: int = 0, sleep: float = 0.0):
        self.idx = idx
        self.sleep = sleep

    def __next__(self):
        if self.sleep:
            import time
            time.sleep(self.sleep)
        out = (self.idx,)
        self.idx += 1
        return out

    def state(self):
        return {"idx": self.idx}

    def restore(self, st):
        self.idx = int(st["idx"])


def _mk(depth=2, sleep=0.0, prepare=None):
    from repro.data import Prefetcher
    return Prefetcher(_CountSource(sleep=sleep), prepare=prepare,
                      depth=depth)


def test_prefetcher_preserves_order_and_applies_prepare():
    pf = _mk(prepare=lambda i: i * 10)
    got = [next(pf) for _ in range(7)]
    pf.close()
    assert got == [0, 10, 20, 30, 40, 50, 60]
    assert pf.n_consumed == 7
    assert pf.stall_s >= 0.0


def test_prefetcher_state_excludes_in_flight_batches():
    import time
    pf = _mk(depth=2)
    assert pf.state() == {"idx": 0}          # nothing consumed yet
    assert next(pf) == (0,)
    # give the producer time to run ahead (queue depth 2 + one in hand)
    time.sleep(0.2)
    assert pf.source.idx > 1                 # it DID prefetch ahead
    assert pf.state() == {"idx": 1}          # ...but state() doesn't move
    assert next(pf) == (1,)
    assert pf.state() == {"idx": 2}
    pf.close()


def test_prefetcher_close_rewinds_so_nothing_is_skipped():
    import time
    pf = _mk(depth=2)
    assert next(pf) == (0,)
    time.sleep(0.2)                          # let it prefetch 1, 2
    pf.close()                               # drops them, rewinds source
    assert pf.source.idx == 1
    assert next(pf) == (1,)                  # regenerated, not skipped
    pf.close()


def test_prefetcher_restore_is_batch_exact():
    pf = _mk(depth=2)
    want = [next(pf) for _ in range(5)]
    st = pf.state()
    _ = [next(pf) for _ in range(3)]         # wander ahead
    pf.restore(st)
    got = [next(pf) for _ in range(3)]
    pf.close()
    assert st == {"idx": 5}
    assert got == [(5,), (6,), (7,)]
    assert want == [(i,) for i in range(5)]


def test_prefetcher_propagates_producer_errors():
    import time
    from repro.data import Prefetcher

    class _Boom(_CountSource):
        def __next__(self):
            if self.idx == 2:
                raise RuntimeError("sampler exploded")
            return super().__next__()

    pf = Prefetcher(_Boom(), depth=1)
    assert next(pf) == (0,)
    # depth 1 forces the full-queue timing: the producer hits the error
    # while item 1 still occupies the queue, so the DONE sentinel must
    # wait for space — a dropped sentinel here would hang the consumer
    # forever with the error lost
    time.sleep(0.3)
    assert next(pf) == (1,)
    with pytest.raises(RuntimeError, match="sampler exploded"):
        next(pf)
    pf.close()


def test_prefetcher_rejects_bad_depth():
    from repro.data import Prefetcher
    with pytest.raises(ValueError, match="depth"):
        Prefetcher(_CountSource(), depth=0)


def test_prefetcher_close_is_idempotent():
    pf = _mk(depth=2)
    assert next(pf) == (0,)
    pf.close()
    pf.close()                               # double close: a no-op
    pf.close()
    assert pf.source.idx == 1                # still rewound to last consumed
    assert next(pf) == (1,)                  # and still restartable
    pf.close()
    # close before ever starting the producer is also fine
    fresh = _mk()
    fresh.close()
    assert next(fresh) == (0,)
    fresh.close()


def test_prefetcher_close_after_producer_error_discards_it():
    import time
    from repro.data import Prefetcher

    class _Boom(_CountSource):
        def __next__(self):
            if self.idx == 1:
                raise RuntimeError("sampler exploded")
            return super().__next__()

    pf = Prefetcher(_Boom(), depth=1)
    assert next(pf) == (0,)
    time.sleep(0.3)                          # let the producer die
    pf.close()                               # error discarded, queue drained
    pf.close()                               # and still idempotent
    assert pf._error is None
    # the rewound source re-raises on the NEXT consume — the error is
    # regenerated, never silently lost
    with pytest.raises(RuntimeError, match="sampler exploded"):
        next(pf)
    pf.close()


# ---------------------------------------------------------------------------
# BatchChain: one placed batch ahead at most, and the hand-off span.
# ---------------------------------------------------------------------------
class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name and arguments."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **kw):
        import contextlib
        self.seen.append((name, kw))
        return contextlib.nullcontext()

    def of(self, name):
        return [kw for n, kw in self.seen if n == name]


def _chain(place_sleep=0.0, depth=2):
    """A BatchChain over the count source: batch ``i`` comes out as
    ``{"batch": i, "f": 10 * i}``; ``placed`` lists the place stage's
    calls."""
    import time
    from repro.data import BatchChain
    placed = []

    def place(host):
        placed.append(host["batch"])
        time.sleep(place_sleep)
        return dict(host)

    chain = BatchChain(_CountSource(), lambda i: (i, i * 10),
                       lambda i, f: {"batch": i, "f": f}, place, depth=depth)
    return chain, placed


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_chain_places_at_most_one_batch_ahead_of_a_slow_consumer(depth):
    import time
    chain, placed = _chain(depth=depth)
    ahead = []
    for i in range(6):
        assert next(chain)["batch"] == i
        time.sleep(0.05)                 # the step: the chain runs ahead
        ahead.append(len(placed) - (i + 1))
    chain.close()
    # the host stages ran ahead by their queues; placement by one batch
    assert max(ahead) == 1 and min(ahead) >= 0, ahead
    assert chain.source.idx == 6         # rewound to the last consumed


def test_chain_handoff_is_ready_for_a_slow_consumer(monkeypatch):
    import time
    import jax
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    chain, _ = _chain()
    for i in range(5):
        assert next(chain)["batch"] == i
        time.sleep(0.1)
    chain.close()
    handoffs = spans.of("pipeline.handoff")
    assert [h["batch"] for h in handoffs[:5]] == list(range(5))
    # the first hand-off meets the loop waiting for it; every later batch
    # is placed while the loop is busy
    assert [h["ready"] for h in handoffs[1:]] == [1] * (len(handoffs) - 1)


def test_chain_handoff_is_not_ready_for_a_slow_producer(monkeypatch):
    import jax
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    chain, _ = _chain(place_sleep=0.15)
    got = [next(chain)["batch"] for _ in range(4)]
    chain.close()
    assert got == [0, 1, 2, 3]
    handoffs = spans.of("pipeline.handoff")
    assert [h["batch"] for h in handoffs] == got
    assert [h["ready"] for h in handoffs] == [0] * 4


# ---------------------------------------------------------------------------
# Trainer: sync == prefetch, metrics, multilabel.
# ---------------------------------------------------------------------------
def _toy_trainer(pipeline: str, ckpt=None, spec="coo+serial", seed=3,
                 dataset="flickr", **kw):
    from repro.launch.trainer import Trainer
    kw.setdefault("feat_dim", 16)
    kw.setdefault("scale", 0.005)
    return Trainer(spec, dataset, n_cores=1,
                   hidden=16, batch_size=16, lr=0.2, seed=seed,
                   input_pipeline=pipeline, val_batches=1,
                   ckpt_dir=ckpt, ckpt_every=0, **kw)


def test_trainer_sync_and_prefetch_streams_are_identical():
    a = _toy_trainer("prefetch").fit(1, steps_per_epoch=6)
    b = _toy_trainer("sync").fit(1, steps_per_epoch=6)
    assert a["loss_history"] == b["loss_history"]
    # dense features run through the staged chain, as a store does
    assert set(a["stage_stall_s_per_step"]) == {"sample", "gather",
                                                "layout", "place"}
    assert len(a["loss_history"]) == 6
    assert 0.0 <= a["val_acc"][0] <= 1.0
    for key in ("epoch_s", "steps_per_s", "host_stall_s_per_step"):
        assert len(a[key]) == 1 and a[key][0] >= 0.0
    assert a["input_pipeline"] == "prefetch"
    assert b["input_pipeline"] == "sync"


def test_trainer_chain_source_and_prepare_can_be_replaced():
    """A source assigned to ``fetcher.source`` after construction is what
    the chain draws from, and a wrapped ``fetcher.prepare`` is what runs,
    called with ``(mb, feats, labels)``; the loop's stall is the wait on
    the last stage."""
    tr = _toy_trainer("prefetch")
    ref = _toy_trainer("sync")

    class Replay:
        def __init__(self, pipeline, order):
            self.pipeline, self.order, self.i = pipeline, order, 0

        def __next__(self):
            self.pipeline.restore({"seed": self.pipeline.seed, "epoch": 0,
                                   "batch_idx": self.order[self.i]})
            self.i += 1
            return next(self.pipeline)

        def state(self):
            return {"i": self.i}

        def restore(self, st):
            self.i = int(st.get("i", 0))

    order = [4, 1, 3]
    tr.fetcher.source = Replay(tr.pipeline, order)
    tr.fetcher.restore({"i": 0})
    prepare, seen = tr.fetcher.prepare, []

    def wrapped(*item):
        seen.append(item)
        return prepare(*item)

    tr.fetcher.prepare = wrapped
    tr.reset_stall_stats()
    losses = tr.train_steps(3)
    assert tr.stall_per_step == tr.fetcher.stall_per_step >= 0.0
    tr.close()
    assert [len(item) for item in seen[:3]] == [3, 3, 3]
    assert [item[0].batch for item in seen[:3]] == order
    # the same batches, drawn by the sync path, train to the same losses
    want = []
    for idx in order:
        ref.pipeline.restore({"seed": ref.pipeline.seed, "epoch": 0,
                              "batch_idx": idx})
        want += ref.train_steps(1)
    assert losses == want


def test_trainer_places_at_most_one_batch_ahead():
    """With a step loop slower than the chain, ``commit_batch`` calls
    never run more than one ahead of the batches consumed."""
    import time
    tr = _toy_trainer("prefetch")
    commit, calls = tr.bundle.commit_batch, []

    def counting(host):
        calls.append(host["batch"])
        return commit(host)

    tr.bundle.commit_batch = counting
    ahead = []
    for consumed in range(1, 5):
        next(tr.fetcher)
        # the step: the chain places the next batch, and runs on as far
        # as it may
        deadline = time.monotonic() + 20
        while len(calls) <= consumed and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.5)
        ahead.append(len(calls) - consumed)
    tr.close()
    assert ahead == [1] * 4, ahead


def test_sampled_batches_hold_no_device_arrays():
    """A batch queued between the chain's host stages holds only host
    arrays: the sampled layers leave the device in the sample stage."""
    import jax
    tr = _toy_trainer("prefetch")
    mb, labels = next(tr.pipeline)
    tr.close()
    leaves = jax.tree_util.tree_leaves(mb.layers) + [labels,
                                                     mb.input_nodes]
    assert leaves and all(type(a) is np.ndarray for a in leaves)


def test_trainer_multilabel_dataset_trains():
    out = _toy_trainer("prefetch", dataset="yelp", scale=0.0005,
                       seed=0).fit(1, steps_per_epoch=2)
    assert len(out["loss_history"]) == 2
    assert all(np.isfinite(out["loss_history"]))


def test_trainer_rejects_bad_input_pipeline():
    # validated before any dataset/mesh work happens
    with pytest.raises(ValueError, match="input_pipeline"):
        _toy_trainer("turbo")


# ---------------------------------------------------------------------------
# Resume-exactness THROUGH the prefetcher (mid-epoch, batches in flight).
# ---------------------------------------------------------------------------
def test_trainer_resume_through_prefetcher_is_bit_exact(tmp_path):
    """Checkpoint mid-epoch while the producer holds prefetched batches in
    flight; restore must replay the exact remaining batch stream (pipeline
    states step for step) and the exact loss trajectory."""
    from repro.data import BatchChain
    full = _toy_trainer("prefetch")
    full_losses, full_states = [], []
    for _ in range(10):
        full_losses.extend(full.train_steps(1))
        full_states.append(full._pipeline_state())
    full.close()

    part = _toy_trainer("prefetch", ckpt=str(tmp_path))
    assert isinstance(part.fetcher, BatchChain)
    part.train_steps(4)
    # the producer thread has had time to run ahead; the saved state must
    # nevertheless point at batch 5 (last consumed), not at the queue head
    part.save(sync=True)
    part.close()

    resumed = _toy_trainer("prefetch", ckpt=str(tmp_path))
    assert resumed.resume() is True
    assert resumed.global_step == 4
    assert resumed._pipeline_state() == full_states[3]
    res_losses, res_states = [], []
    for _ in range(6):
        res_losses.extend(resumed.train_steps(1))
        res_states.append(resumed._pipeline_state())
    resumed.close()
    # bit-identical loss trajectory AND batch stream
    assert res_losses == full_losses[4:]
    assert res_states == full_states[4:]


def test_trainer_fit_resume_continues_to_same_horizon(tmp_path):
    full = _toy_trainer("prefetch").fit(1, steps_per_epoch=8)
    part = _toy_trainer("prefetch", ckpt=str(tmp_path))
    part.train_steps(5)
    part.save(sync=True)
    part.close()
    out = _toy_trainer("prefetch", ckpt=str(tmp_path)).fit(
        1, steps_per_epoch=8, max_steps=8, resume=True)
    assert out["loss_history"] == full["loss_history"][5:]
    assert out["global_step"] == 8


# ---------------------------------------------------------------------------
# Every registered spec trains end-to-end on 2 simulated devices (ISSUE-4
# acceptance bar: trajectories within 1e-4 of the coo+serial oracle).
# ---------------------------------------------------------------------------
def test_trainer_every_spec_matches_oracle_on_two_devices():
    run_subprocess(textwrap.dedent("""
        from repro.engine import supported_specs
        from repro.launch.trainer import Trainer

        def run(spec):
            tr = Trainer(spec, 'flickr', n_cores=2, scale=0.005,
                         feat_dim=16, hidden=16, batch_size=16, lr=0.2,
                         seed=0, input_pipeline='prefetch', val_batches=1)
            out = tr.fit(1, steps_per_epoch=4)
            return out['loss_history'], out['val_acc'][0]

        # padding that can't split across the hypercube dies at init
        try:
            Trainer('coo+serial', 'flickr', n_cores=2, scale=0.005,
                    feat_dim=16, pad_multiple=17)
            raise SystemExit('expected ValueError for pad_multiple=17')
        except ValueError as e:
            assert 'multiple of' in str(e), e

        specs = supported_specs()
        assert len(specs) >= 3, specs
        ref, ref_acc = run('coo+serial')
        for spec in specs:
            traj, acc = run(spec)
            drift = max(abs(a - b) for a, b in zip(ref, traj))
            assert drift <= 1e-4, (spec, drift, ref, traj)
            assert abs(acc - ref_acc) <= 0.5, (spec, acc, ref_acc)
        print('OK', specs)
    """), n_devices=2)


def test_reexec_launchers_refuse_a_tpu_backend(monkeypatch):
    """A launcher that re-execs itself on forced host devices must not
    start a child on a TPU host (the parent holds the chip)."""
    import jax
    from repro.launch.mesh import forced_host_env

    env = forced_host_env(4, "src")
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["PYTHONPATH"].startswith("src")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        forced_host_env(4, "src")


def test_compile_cache_dir_is_fixed_or_the_env_one(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper leaves JAX's own
    setting alone; without it, the cache goes to the checkout's fixed
    ``.jax_cache`` — never a temporary or per-process path."""
    import os

    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == path
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
