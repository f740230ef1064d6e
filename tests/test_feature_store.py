"""Out-of-core feature store + hot-vertex cache + staged pipeline.

Contracts:
  * the ``@register_store`` registry mirrors the engine registry —
    unknown names fail loudly listing the options, fresh registrations
    are reachable with no other code change;
  * ``host`` and ``mmap`` backends gather bit-exactly, count their
    traffic, round-trip the chunked writer, and refuse writes after
    ``seal()``;
  * ``make_dataset(features="store"/"mmap")`` generates features (and
    labels) BIT-IDENTICAL to the dense path at the same seed;
  * the :class:`HotVertexCache` is bit-exact with the raw store, its
    hit/miss/eviction accounting is exact, and eviction can never touch
    a pinned row;
  * :class:`StagedPrefetcher` preserves ordering and the batch-exact
    ``(seed, epoch, batch_idx)`` restore contract through a multi-stage
    chain;
  * the Trainer trains from a store (sync == staged prefetch == dense,
    bit-equal losses), enforces the simulated device feature budget, and
    checkpoint/resumes through the staged store pipeline bit-exactly;
  * every registered spec trains from an MmapStore on 2 simulated
    devices within 1e-5 of its in-memory trajectory.
"""
import textwrap

import numpy as np
import pytest

from conftest import run_subprocess


# ---------------------------------------------------------------------------
# Registry contract (mirrors engine/registry.py).
# ---------------------------------------------------------------------------
def test_unknown_store_fails_loudly_listing_options():
    from repro.featurestore import get_store
    with pytest.raises(ValueError, match=r"unknown feature store 'ssd'"):
        get_store("ssd")
    with pytest.raises(ValueError, match="host"):
        get_store("ssd")          # the error names the registered options


def test_fresh_registration_is_reachable():
    from repro.featurestore import (FeatureStore, available_stores,
                                    get_store, register_store)
    from repro.featurestore.store import _STORES

    @register_store("testonly")
    class _TestStore(FeatureStore):
        pass

    try:
        assert get_store("testonly") is _TestStore
        assert _TestStore.name == "testonly"
        assert "testonly" in available_stores()
    finally:
        _STORES.pop("testonly", None)


def test_builtin_backends_registered():
    from repro.featurestore import (HostStore, MmapStore, available_stores,
                                    get_store)
    assert {"host", "mmap"} <= set(available_stores())
    assert get_store("host") is HostStore
    assert get_store("mmap") is MmapStore


# ---------------------------------------------------------------------------
# Backend gather exactness + facade + counters + writer round-trip.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["host", "mmap"])
def test_store_gather_bit_exact_and_counted(backend, rng):
    from repro.featurestore import get_store
    ref = rng.standard_normal((50, 8)).astype(np.float32)
    with get_store(backend).from_array(ref, chunk_rows=16) as store:
        # ndarray facade
        assert store.shape == (50, 8) and store.ndim == 2
        assert len(store) == 50 and store.nbytes == ref.nbytes
        assert store.dtype == np.float32
        idx = np.array([0, 49, 3, 3, 17])
        got = store.gather(idx)
        np.testing.assert_array_equal(got, ref[idx])
        np.testing.assert_array_equal(store[idx], ref[idx])  # __getitem__
        np.testing.assert_array_equal(store.as_array(), ref)
        # gather + __getitem__ are counted traffic; as_array is not
        assert store.gather_calls == 2
        assert store.bytes_gathered == got.nbytes * 2


@pytest.mark.parametrize("backend", ["host", "mmap"])
def test_chunked_writer_roundtrip_and_seal(backend, rng):
    from repro.featurestore import get_store
    ref = rng.standard_normal((40, 4)).astype(np.float32)
    store = get_store(backend).create(40, 4)
    for s in range(0, 40, 13):
        store.write_chunk(s, ref[s:s + 13])
    store.seal()
    try:
        np.testing.assert_array_equal(store.as_array(), ref)
        with pytest.raises(ValueError, match="sealed"):
            store.write_chunk(0, ref[:1])
    finally:
        store.close()


def test_writer_rejects_bad_chunks():
    from repro.featurestore import HostStore
    store = HostStore.create(10, 4)
    with pytest.raises(ValueError, match="feat_dim"):
        store.write_chunk(0, np.zeros((2, 5), np.float32))
    with pytest.raises(ValueError, match="out of range"):
        store.write_chunk(8, np.zeros((3, 4), np.float32))


def test_mmap_store_reopens_from_path(tmp_path, rng):
    from repro.featurestore import MmapStore
    ref = rng.standard_normal((30, 6)).astype(np.float32)
    path = str(tmp_path / "feats.npy")
    MmapStore.from_array(ref, path=path).close()
    store = MmapStore.open(path)          # .npy header carries shape/dtype
    try:
        assert store.shape == (30, 6)
        np.testing.assert_array_equal(store.as_array(), ref)
        with pytest.raises(ValueError, match="sealed"):
            store.write_chunk(0, ref[:1])
    finally:
        store.close()
    assert (tmp_path / "feats.npy").exists()   # non-owned path survives


def test_mmap_tempfile_unlinked_on_close(rng):
    import os
    from repro.featurestore import MmapStore
    store = MmapStore.from_array(
        rng.standard_normal((8, 2)).astype(np.float32))
    path = store.path
    assert os.path.exists(path)
    store.close()
    assert not os.path.exists(path)
    store.close()                          # idempotent


# ---------------------------------------------------------------------------
# make_dataset(features=...): store-backed generation is bit-identical.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("features", ["store", "mmap"])
def test_make_dataset_store_bit_identical_to_dense(features):
    from repro.featurestore import FeatureStore
    from repro.graph import make_dataset
    dense = make_dataset("flickr", scale=0.003, seed=7, feat_dim=12)
    ds = make_dataset("flickr", scale=0.003, seed=7, feat_dim=12,
                      features=features, chunk_rows=50)  # force many chunks
    try:
        assert isinstance(ds.features, FeatureStore)
        np.testing.assert_array_equal(ds.features.as_array(), dense.features)
        # labels are drawn AFTER features from the same stream — the
        # chunked generation must leave the generator in the same spot
        np.testing.assert_array_equal(ds.labels, dense.labels)
        np.testing.assert_array_equal(ds.graph.indptr, dense.graph.indptr)
    finally:
        ds.features.close()


# ---------------------------------------------------------------------------
# HotVertexCache: exactness, accounting, pinned rows are untouchable.
# ---------------------------------------------------------------------------
def _cache(n=20, d=4, capacity=4, pinned=2, rng=None):
    from repro.featurestore import HostStore, HotVertexCache
    rng = rng or np.random.default_rng(0)
    ref = rng.standard_normal((n, d)).astype(np.float32)
    store = HostStore.from_array(ref)
    degrees = np.arange(n, 0, -1)          # vertex 0 is the hottest
    return HotVertexCache(store, degrees, capacity, pinned=pinned), store, ref


def test_cache_hit_accounting_is_exact():
    cache, store, ref = _cache()
    assert cache.pinned_ids == {0, 1}      # top-degree, deterministic
    got = cache.gather([0, 1, 2, 3])       # 2 pinned hits, 2 misses
    np.testing.assert_array_equal(got, ref[[0, 1, 2, 3]])
    assert (cache.hits, cache.misses, cache.evictions) == (2, 2, 0)
    got = cache.gather([0, 2, 3, 5])       # 3 hits, miss 5 evicts LRU (2)
    np.testing.assert_array_equal(got, ref[[0, 2, 3, 5]])
    assert (cache.hits, cache.misses, cache.evictions) == (5, 3, 1)
    cache.gather([2])                      # evicted above: a miss again
    assert (cache.hits, cache.misses) == (5, 4)
    assert cache.hit_rate == 5 / 9
    # duplicates count as absorbed traffic, one row per repeat
    cache.gather([0, 0, 0])
    assert cache.hits == 8
    stats = cache.stats()
    assert stats["hits"] == 8 and stats["misses"] == 4
    assert stats["bytes_served"] == 12 * 4 * 4
    assert stats["bytes_from_store"] == store.bytes_gathered \
        - cache.warm_bytes


def test_cache_never_evicts_pinned_rows(rng):
    cache, store, ref = _cache(n=64, capacity=6, pinned=3, rng=rng)
    pinned = sorted(cache.pinned_ids)
    assert pinned == [0, 1, 2]
    # churn the dynamic region far past its 3 slots
    for _ in range(20):
        cache.gather(rng.integers(3, 64, size=8))
    assert cache.evictions > 0
    before = store.bytes_gathered
    got = cache.gather(pinned)             # must be pure hits
    np.testing.assert_array_equal(got, ref[pinned])
    assert store.bytes_gathered == before  # zero store traffic
    assert set(pinned) <= set(cache._slot)


def test_cache_gather_bit_exact_on_random_frontiers():
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -e .[test])")
    st = pytest.importorskip("hypothesis.strategies")

    cache, store, ref = _cache(n=32, capacity=8, pinned=4)

    @hypothesis.settings(max_examples=50, deadline=None)
    @hypothesis.given(st.lists(st.integers(min_value=0, max_value=31),
                               min_size=1, max_size=24))
    def prop(ids):
        np.testing.assert_array_equal(cache.gather(ids),
                                      ref[np.asarray(ids)])

    prop()


def test_cache_rejects_bad_shapes():
    from repro.featurestore import HostStore, HotVertexCache
    store = HostStore.from_array(np.zeros((10, 2), np.float32))
    with pytest.raises(ValueError, match="capacity"):
        HotVertexCache(store, np.ones(10), 0)
    with pytest.raises(ValueError, match="degrees"):
        HotVertexCache(store, np.ones(9), 4)


# ---------------------------------------------------------------------------
# StagedPrefetcher: ordering + restore through a multi-stage chain.
# ---------------------------------------------------------------------------
class _CountSource:
    def __init__(self):
        self.idx = 0

    def __next__(self):
        out = (self.idx,)
        self.idx += 1
        return out

    def state(self):
        return {"idx": self.idx}

    def restore(self, st):
        self.idx = int(st["idx"])


def _staged(depth=2):
    from repro.data import StagedPrefetcher
    return StagedPrefetcher(
        _CountSource(),
        [("double", lambda i: (i * 2,)), ("plus1", lambda i: i + 1)],
        depth=depth)


def test_staged_prefetcher_orders_and_composes_stages():
    sp = _staged()
    got = [next(sp) for _ in range(6)]
    sp.close()
    assert got == [1, 3, 5, 7, 9, 11]      # (i*2)+1, in order
    assert sp.n_consumed == 6
    assert set(sp.stage_stalls()) == {"double", "plus1"}


def test_staged_prefetcher_restore_is_batch_exact():
    sp = _staged()
    want = [next(sp) for _ in range(4)]
    st = sp.state()
    assert st == {"idx": 4}                # innermost source, consumed only
    _ = [next(sp) for _ in range(3)]       # wander ahead, stages in flight
    sp.restore(st)
    got = [next(sp) for _ in range(3)]
    sp.close()
    assert want == [1, 3, 5, 7]
    assert got == [9, 11, 13]              # regenerated, never skipped


def test_staged_prefetcher_close_rewinds_all_stages():
    import time
    sp = _staged()
    assert next(sp) == 1
    time.sleep(0.2)                        # let every stage run ahead
    sp.close()
    assert sp.source.idx == 1              # rewound through the chain
    assert next(sp) == 3
    sp.close()


def test_staged_prefetcher_validates_stages():
    from repro.data import StagedPrefetcher
    with pytest.raises(ValueError, match="at least one stage"):
        StagedPrefetcher(_CountSource(), [])
    with pytest.raises(ValueError, match="duplicate"):
        StagedPrefetcher(_CountSource(),
                         [("a", int), ("a", int)])


# ---------------------------------------------------------------------------
# Trainer integration: store == dense, budgets, resume through the chain.
# ---------------------------------------------------------------------------
def _store_trainer(pipeline, feature_store=None, ckpt=None,
                   dataset="flickr", **kw):
    from repro.launch.trainer import Trainer
    if isinstance(dataset, str):
        kw.setdefault("scale", 0.005)
        kw.setdefault("feat_dim", 16)
    return Trainer("coo+serial", dataset, n_cores=1, hidden=16,
                   batch_size=16, lr=0.2, seed=3, input_pipeline=pipeline,
                   val_batches=1, feature_store=feature_store,
                   ckpt_dir=ckpt, ckpt_every=0, **kw)


def test_trainer_store_streams_match_dense_bit_exact():
    ref = _store_trainer("sync").fit(1, steps_per_epoch=5)
    sync = _store_trainer("sync", feature_store="mmap",
                          cache_capacity=32).fit(1, steps_per_epoch=5)
    staged = _store_trainer("prefetch", feature_store="mmap",
                            cache_capacity=32).fit(1, steps_per_epoch=5)
    assert ref["loss_history"] == sync["loss_history"]
    assert ref["loss_history"] == staged["loss_history"]
    assert ref["feature_store"] == "device"
    assert sync["feature_store"] == staged["feature_store"] == "mmap"
    for out in (sync, staged):
        assert out["gather_bytes"] > 0
        assert out["cache"]["hit_rate"] > 0
    # the staged chain reports per-stage stalls; sync has no chain
    assert set(staged["stage_stall_s_per_step"]) \
        == {"sample", "gather", "layout", "place"}
    assert "stage_stall_s_per_step" not in sync


def test_staged_store_chain_places_at_most_one_batch_ahead():
    """The store path's chain keeps the same cap: with a step loop slower
    than the chain, ``commit_batch`` runs at most one batch ahead of the
    batches consumed."""
    import time
    tr = _store_trainer("prefetch", feature_store="mmap", cache_capacity=32)
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


def test_trainer_trains_from_store_backed_dataset():
    from repro.featurestore import FeatureStore
    from repro.graph import make_dataset
    ds = make_dataset("flickr", scale=0.005, seed=3, feat_dim=16,
                      features="store")
    assert isinstance(ds.features, FeatureStore)
    out = _store_trainer("prefetch", dataset=ds).fit(1, steps_per_epoch=3)
    assert out["feature_store"] == "host"   # picked up with no flag
    assert out["gather_bytes"] > 0
    assert all(np.isfinite(out["loss_history"]))


def test_trainer_device_budget_rejects_dense_but_not_store():
    # the dense matrix is ~446*16*4 bytes; a 1 KB budget must refuse it
    with pytest.raises(ValueError, match="device_budget_bytes"):
        _store_trainer("sync", device_budget_bytes=1024)
    # the same budget with a store trains: only frontier rows hit devices
    out = _store_trainer("sync", feature_store="mmap",
                         device_budget_bytes=1024).fit(1, steps_per_epoch=2)
    assert len(out["loss_history"]) == 2


def test_trainer_resume_through_staged_store_pipeline_is_bit_exact(tmp_path):
    """Checkpoint with batches in flight across ALL stages of the staged
    store chain; the resumed run must replay the remaining stream and
    losses bit-exactly — the (seed, epoch, batch_idx) contract survives
    the deeper pipeline."""
    def build(ckpt=None):
        return _store_trainer("prefetch", feature_store="mmap",
                              cache_capacity=32, ckpt=ckpt)

    full = build()
    full_losses = full.train_steps(8)
    full.close()

    part = build(ckpt=str(tmp_path))
    part.train_steps(3)
    part.save(sync=True)        # gather/layout/place queues hold work
    part.close()

    resumed = build(ckpt=str(tmp_path))
    assert resumed.resume() is True
    assert resumed.global_step == 3
    res_losses = resumed.train_steps(5)
    resumed.close()
    assert res_losses == full_losses[3:]


# ---------------------------------------------------------------------------
# Acceptance: every registered spec trains out-of-core on 2 devices, with
# features larger than the simulated per-device budget, ≤1e-5 vs in-memory.
# ---------------------------------------------------------------------------
def test_every_spec_trains_from_mmap_store_on_two_devices():
    run_subprocess(textwrap.dedent("""
        from repro.engine import supported_specs
        from repro.featurestore import MmapStore
        from repro.graph import make_dataset
        from repro.launch.trainer import Trainer

        dense = make_dataset('flickr', scale=0.005, seed=0, feat_dim=16)
        ds = make_dataset('flickr', scale=0.005, seed=0, feat_dim=16,
                          features='mmap')
        assert isinstance(ds.features, MmapStore)
        # the feature matrix exceeds the simulated per-device budget: the
        # dense path refuses, the store path streams frontier rows
        budget = ds.features.nbytes // 4

        def run(spec, dataset, **kw):
            tr = Trainer(spec, dataset, n_cores=2, hidden=16,
                         batch_size=16, lr=0.2, seed=0,
                         input_pipeline='prefetch', val_batches=0,
                         cache_capacity=32, **kw)
            return tr.fit(1, steps_per_epoch=3)

        try:
            run('coo+serial', dense, device_budget_bytes=budget)
            raise SystemExit('dense features over budget must refuse')
        except ValueError as e:
            assert 'device_budget_bytes' in str(e), e

        specs = supported_specs()
        assert len(specs) >= 3, specs
        for spec in specs:
            a = run(spec, dense)['loss_history']
            out = run(spec, ds, device_budget_bytes=budget)
            b = out['loss_history']
            assert out['feature_store'] == 'mmap'
            assert out['cache']['hit_rate'] > 0, spec
            drift = max(abs(x - y) for x, y in zip(a, b))
            assert drift <= 1e-5, (spec, drift, a, b)
        ds.features.close()
        print('OK', specs)
    """), n_devices=2)
