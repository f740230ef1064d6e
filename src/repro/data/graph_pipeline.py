"""Graph mini-batch pipeline: sampler → static-shaped device batches — plus
the background producers (:class:`Prefetcher`, :class:`StagedPrefetcher`,
:class:`BatchChain`) that take the host-side work off the step critical
path.

Wraps :class:`repro.graph.NeighborSampler` into the same restartable-stream
contract as the token pipeline: the epoch permutation is derived from
``(seed, epoch)`` so restore-from-checkpoint replays the exact remaining
batches.  Shapes are padded to the per-layer static maxima so one jit trace
serves every batch (the paper's fixed 1024-node staging serves the same
purpose in BRAM).

:class:`BatchChain` is the software analogue of the paper's NUMA-aware
host-side staging (§4.2–4.3): the four host stages of a batch — sample,
feature gather, layout build, device placement — each run on a thread of
their own, joined by depth-``k`` bounded queues, so the host's pace is its
slowest stage rather than the sum of the four, and batch *i+1*'s host work
overlaps batch *i*'s device step.  Placement stays at most one batch ahead
of the step loop.  Every queue slot carries the pipeline state that
regenerates the NEXT batch, so checkpointing mid-epoch with batches in
flight restores batch-exact.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

from repro.graph.datasets import GraphDataset
from repro.graph.sampler import MiniBatch, NeighborSampler


def sample_batch(dataset: GraphDataset, sampler: NeighborSampler,
                 seeds: np.ndarray, nnz_pad, rng: np.random.Generator,
                 batch: int = -1) -> Tuple[MiniBatch, np.ndarray]:
    """The feature-free half of batch assembly: ``(mb, labels)``, under the
    profiler span ``pipeline.sample``; ``batch`` (the pipeline's batch
    index) tags the span and rides on ``mb.batch``.

    Labels are row-fancy-indexed (single-label ``[n]`` ints and multilabel
    ``[n, c]`` rows alike) with padded seed rows zero-padded — they index
    GLOBAL node 0's label, a placeholder the consumer masks (train loss
    counts only real rows when masked; val accuracy scores only the first
    ``len(seeds)`` rows).  :class:`BatchChain` runs this stage alone and
    gathers features in its own stage.

    The sampled layers come back as host arrays: the sampler builds them
    on the default device, and a batch waiting in a host stage's queue
    must hold no device memory (the layout build reads them on the host
    in any case)."""
    with jax.profiler.TraceAnnotation("pipeline.sample", batch=batch):
        mb = sampler.sample(seeds, nnz_pad=nnz_pad, rng=rng)
        layers = jax.device_get(mb.layers)
        pad = mb.layers[0].n_dst - len(seeds)
        labels = dataset.labels[np.pad(seeds, (0, pad))]
    return dataclasses.replace(mb, layers=layers, batch=batch), labels


def gather_features(features, input_nodes: np.ndarray,
                    n_nodes: int, batch: int = -1) -> np.ndarray:
    """THE frontier-gather rule: clamp-index padded frontier slots to the
    last real node, then fancy-index ``features`` — a dense ndarray, a
    :class:`~repro.featurestore.FeatureStore`, or a
    :class:`~repro.featurestore.HotVertexCache` alike (all three share
    the row-fancy-indexing surface, so one rule serves every tier).
    Runs under the profiler span ``pipeline.gather``, tagged ``batch``."""
    with jax.profiler.TraceAnnotation("pipeline.gather", batch=batch):
        return features[np.minimum(input_nodes, n_nodes - 1)]


def assemble_batch(dataset: GraphDataset, sampler: NeighborSampler,
                   seeds: np.ndarray, nnz_pad, rng: np.random.Generator,
                   batch: int = -1
                   ) -> Tuple[MiniBatch, np.ndarray, np.ndarray]:
    """One sampled batch: ``(mb, features, labels)`` for ``seeds``.

    THE batch-assembly rule, shared by the epoch pipeline and the
    Trainer's validation path so padding/label semantics can never
    diverge: :func:`sample_batch` + :func:`gather_features` fused —
    the staged pipeline calls the two halves as separate stages."""
    mb, labels = sample_batch(dataset, sampler, seeds, nnz_pad, rng, batch)
    feats = gather_features(dataset.features, mb.input_nodes,
                            dataset.graph.n_nodes, batch)
    return mb, feats, labels


@dataclasses.dataclass
class GraphBatchPipeline:
    """Restartable epoch stream of sampled batches.

    ``defer_gather=False`` (default) yields ``(mb, feats, labels)`` —
    features gathered inline, the in-memory path.  ``defer_gather=True``
    yields ``(mb, labels)`` and leaves the feature gather to a downstream
    pipeline stage (the out-of-core store path: sampling must not block
    on host/disk feature traffic it could overlap)."""

    dataset: GraphDataset
    sampler: NeighborSampler
    batch_size: int
    seed: int = 0
    epoch: int = 0
    batch_idx: int = 0
    defer_gather: bool = False

    def _perm(self) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.epoch]))
        return rng.permutation(self.dataset.graph.n_nodes)

    @property
    def batches_per_epoch(self) -> int:
        return self.dataset.graph.n_nodes // self.batch_size

    def __iter__(self) -> Iterator[Tuple[MiniBatch, np.ndarray, np.ndarray]]:
        return self

    def __next__(self):
        perm = self._perm()
        n_batches = len(perm) // self.batch_size
        if self.batch_idx >= n_batches:
            self.epoch += 1
            self.batch_idx = 0
            perm = self._perm()
        s = self.batch_idx * self.batch_size
        seeds = perm[s:s + self.batch_size]
        # per-batch generator keyed by (seed, epoch, batch): resume-exact
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.epoch, self.batch_idx]))
        batch = self.batch_idx
        self.batch_idx += 1
        nnz_pad = self.sampler.static_nnz(self.batch_size)
        if self.defer_gather:
            return sample_batch(self.dataset, self.sampler, seeds,
                                nnz_pad, rng, batch)
        return assemble_batch(self.dataset, self.sampler, seeds,
                              nnz_pad, rng, batch)

    def state(self) -> Dict[str, int]:
        return {"seed": self.seed, "epoch": self.epoch,
                "batch_idx": self.batch_idx}

    def restore(self, state: Dict[str, int]) -> None:
        self.seed = int(state["seed"])
        self.epoch = int(state["epoch"])
        self.batch_idx = int(state["batch_idx"])


class Prefetcher:
    """Depth-``k`` background producer over a restartable batch source.

    ``source`` is any iterator with the pipeline contract (``__next__`` +
    ``state()``/``restore()``); ``prepare`` is the per-item host transform
    (one stage of a :class:`StagedPrefetcher`, or a whole batch's host
    work) run ON THE PRODUCER THREAD, so the consumer's only cost is the
    queue pop.

    Restart contract: every queue slot carries ``source.state()`` captured
    AFTER its batch was drawn — i.e. the state that regenerates the *next*
    batch.  ``state()`` returns the snapshot belonging to the last consumed
    batch, so checkpoint-then-restore replays exactly the batches still in
    flight (queued but unconsumed work is regenerated, never skipped or
    double-consumed).

    Stall accounting: ``stall_s`` accumulates the time ``__next__`` spent
    blocked on the queue — the host time the device step could not hide.
    A sync loop doing the same work inline would stall for the full
    sample+build+place cost every step; the difference is the overlap win
    the ``epoch_time --input-pipeline`` benchmark records.
    """

    _DONE = object()

    def __init__(self, source, prepare: Optional[Callable[..., Any]] = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.source = source
        self.prepare = prepare
        self.depth = depth
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._consumed_state = source.state()
        self.stall_s = 0.0
        self.n_consumed = 0

    # -- producer -----------------------------------------------------------
    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                raw = next(self.source)
                state_after = self.source.state()
                if not self._await_turn():
                    return
                item = raw
                if self.prepare is not None:
                    item = self.prepare(*raw) if isinstance(raw, tuple) \
                        else self.prepare(raw)
                self._hand(raw, (state_after, item))
        except BaseException as e:  # noqa: BLE001 — re-raised on the consumer
            self._error = e
            # deliver the sentinel with the same retry-until-stop put as a
            # normal item: the queue is usually FULL when the producer dies
            # (device step slower than host work), and dropping the
            # sentinel there would leave the consumer blocked on get()
            # forever with the original exception lost
            self._put((None, self._DONE))

    def _await_turn(self) -> bool:
        """Whether the producer may prepare the item it holds (False once
        stopped); the bounded queue alone paces a plain stage."""
        return True

    def _hand(self, raw, entry) -> None:
        """Queue a prepared ``(state_after, item)``; ``raw`` is the item
        as drawn from the source."""
        self._put(entry)

    def _put(self, entry) -> None:
        # bounded put; poll the stop flag so close() never deadlocks
        # against a full queue
        while not self._stop.is_set():
            try:
                self._q.put(entry, timeout=0.05)
                return
            except queue.Full:
                continue

    def _take(self):
        return self._q.get()

    def _ensure_started(self) -> None:
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._produce,
                                            daemon=True)
            self._thread.start()

    # -- consumer -----------------------------------------------------------
    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        self._ensure_started()
        t0 = time.perf_counter()
        state_after, item = self._take()
        self.stall_s += time.perf_counter() - t0
        if item is self._DONE:
            err, self._error = self._error, None
            self._thread = None
            raise err if err is not None else StopIteration
        self._consumed_state = state_after
        self.n_consumed += 1
        return item

    def reset_stats(self) -> None:
        self.stall_s = 0.0
        self.n_consumed = 0

    @property
    def stall_per_step(self) -> float:
        return self.stall_s / max(self.n_consumed, 1)

    # -- restartable-stream contract ----------------------------------------
    def state(self) -> Dict[str, int]:
        """The source state as of the last CONSUMED batch — in-flight
        (prefetched but unconsumed) batches are excluded, so a restore
        regenerates them."""
        return dict(self._consumed_state)

    def restore(self, state: Dict[str, int]) -> None:
        """Drain the queue, rewind the source, restart production lazily."""
        self.close()
        self.source.restore(state)
        self._consumed_state = self.source.state()

    def close(self) -> None:
        """Stop the producer, drop any queued batches (and any pending
        producer error), and rewind the source to the last CONSUMED batch
        — dropped in-flight work is regenerated on the next ``__next__``,
        never skipped, so stop/start (or checkpoint/restore) keeps the
        stream exact.

        Idempotent and exception-safe: a double close, or a close after
        the producer died (its error is discarded — consume via
        ``__next__`` to observe it), is a no-op beyond re-asserting the
        rewound source state.  The staged store pipeline closes stages
        through cascading restores, so repeated closes are its NORMAL
        path, not an error."""
        thread, self._thread = self._thread, None
        try:
            if thread is not None:
                self._stop.set()
                while thread.is_alive():  # unblock a put-blocked producer
                    try:
                        self._q.get_nowait()
                    except queue.Empty:
                        pass
                    thread.join(timeout=0.05)
        finally:
            # queue drain + source rewind run even if the join above blew
            # up — a half-closed prefetcher must never hold stale batches
            self._error = None
            while True:                   # leave the queue empty for restart
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    break
            self.source.restore(self._consumed_state)

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _Handoff(Prefetcher):
    """The last stage of a :class:`StagedPrefetcher`: the one that hands
    finished items to the consumer (placed device batches, in a
    :class:`BatchChain`).

    It prepares an item only once the consumer has taken the previous
    one, so at most one finished item waits beside the one the consumer
    is using: device memory holds the running step's batch plus one more,
    whatever the queue depth.  Each hand-off runs under the profiler span
    ``pipeline.handoff``, tagged ``batch`` (the raw item's ``"batch"``
    entry, -1 without one) and ``ready``: 1 when no consumer was waiting
    on the queue at that moment, 0 when one was blocked for it."""

    def __init__(self, source, prepare=None, depth: int = 2):
        super().__init__(source, prepare=prepare, depth=depth)
        self._taken = threading.Event()    # nothing handed and not taken
        self._taken.set()
        self._waiting = threading.Event()  # the consumer is blocked on get

    def _await_turn(self) -> bool:
        while not self._taken.wait(timeout=0.05):
            if self._stop.is_set():
                return False
        return not self._stop.is_set()

    def _hand(self, raw, entry) -> None:
        batch = raw.get("batch", -1) if isinstance(raw, dict) else -1
        ready = int(not self._waiting.is_set())
        # cleared BEFORE the put: the consumer sets it after its get, so
        # a take that follows the put at once is never lost
        self._taken.clear()
        with jax.profiler.TraceAnnotation("pipeline.handoff", batch=batch,
                                          ready=ready):
            self._put(entry)

    def _take(self):
        self._waiting.set()
        try:
            return self._q.get()
        finally:
            self._waiting.clear()
            self._taken.set()

    def close(self) -> None:
        super().close()
        self._taken.set()                  # the drained queue holds nothing


class StagedPrefetcher:
    """Multi-stage producer chain — the depth-2 double buffer grown into a
    pipeline of named stages, each on its own thread with its own bounded
    queue.

    ``stages`` is a sequence of ``(name, fn)``; stage ``k``'s
    :class:`Prefetcher` consumes stage ``k-1``'s output (``fn`` None: the
    stage only draws from its upstream, so the first stage can run the
    source itself on a thread of its own).  With :class:`BatchChain`'s
    ``sample | gather | layout | place``, batch *i+3*'s sampling overlaps
    batch *i+2*'s feature gather, batch *i+1*'s layout build and
    placement, and batch *i*'s device step — the staged analogue of the
    paper's host-side NUMA staging.  The last stage hands over at most one
    finished item ahead of the consumer (:class:`_Handoff`).

    The restartable-stream contract survives the depth: every queue slot
    in every stage carries the SOURCE state that regenerates its batch
    (Prefetchers chain their ``state()``/``restore()`` verbatim), so
    :meth:`state` is the innermost source's state as of the last batch
    consumed from the LAST stage — all in-flight work in every queue is
    excluded and regenerated on restore, preserving the batch-exact
    ``(seed, epoch, batch_idx)`` checkpoint contract.

    Stall accounting: :attr:`stall_per_step` is the LAST stage's stall —
    the only host time the device step actually sees; :meth:`stage_stalls`
    breaks the hidden time down per stage for the benchmarks.
    """

    def __init__(self, source, stages, depth: int = 2):
        if not stages:
            raise ValueError("StagedPrefetcher needs at least one stage")
        self.names: Tuple[str, ...] = tuple(name for name, _ in stages)
        if len(set(self.names)) != len(self.names):
            raise ValueError(f"duplicate stage names: {list(self.names)}")
        self.stages: list = []
        cur = source
        for k, (_, fn) in enumerate(stages):
            stage = _Handoff if k == len(stages) - 1 else Prefetcher
            cur = stage(cur, prepare=fn, depth=depth)
            self.stages.append(cur)
        self._tail: Prefetcher = cur

    @property
    def source(self):
        """The batch source the first stage draws from.  Assigning one
        closes the chain and starts it afresh at the new source's current
        state (``restore`` then positions it)."""
        return self.stages[0].source

    @source.setter
    def source(self, source) -> None:
        self.close()
        self.stages[0].source = source
        state = source.state()
        for st in self.stages:
            st._consumed_state = dict(state)

    # -- consumer -----------------------------------------------------------
    def __iter__(self) -> "StagedPrefetcher":
        return self

    def __next__(self):
        return next(self._tail)

    @property
    def stall_s(self) -> float:
        return self._tail.stall_s

    @property
    def n_consumed(self) -> int:
        return self._tail.n_consumed

    @property
    def stall_per_step(self) -> float:
        return self._tail.stall_per_step

    def stage_stalls(self) -> Dict[str, float]:
        """Per-stage stall seconds per consumed item (stage k's stall =
        time it spent waiting on stage k-1 — where the pipeline is
        actually bottlenecked)."""
        return {name: st.stall_per_step
                for name, st in zip(self.names, self.stages)}

    def reset_stats(self) -> None:
        for st in self.stages:
            st.reset_stats()

    # -- restartable-stream contract ----------------------------------------
    def state(self) -> Dict[str, int]:
        return self._tail.state()

    def restore(self, state: Dict[str, int]) -> None:
        """Cascades down the chain: every stage drains its queue, then the
        innermost source rewinds to ``state``."""
        self._tail.restore(state)

    def close(self) -> None:
        """Close every stage (tail first — each stage's close rewinds its
        upstream, cascading to the source; Prefetcher.close is idempotent,
        so the overlapping rewinds are safe)."""
        self._tail.close()

    def __enter__(self) -> "StagedPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class BatchChain(StagedPrefetcher):
    """The training input chain: ``sample | gather | layout | place``,
    each stage on its own thread.

    ``source`` yields ``(mb, labels)`` (a :class:`GraphBatchPipeline` with
    ``defer_gather=True``) and is drawn on the sample stage's thread;
    ``gather(mb, labels) -> (mb, feats, labels)`` reads the frontier's
    feature rows (a dense array or an out-of-core store alike);
    :attr:`prepare` ``(mb, feats, labels)`` builds the host layout;
    ``place`` puts it on the devices, at most one batch ahead of the step
    loop.  Sampling and gathering sit on different threads, so the host's
    pace is its slowest stage, not the sum of the two."""

    STAGES = ("sample", "gather", "layout", "place")

    def __init__(self, source, gather: Callable, prepare: Callable,
                 place: Callable, depth: int = 2):
        super().__init__(source, list(zip(
            self.STAGES, (None, gather, prepare, place))), depth=depth)

    @property
    def prepare(self) -> Callable:
        """The layout stage's callable; assigning one replaces it from the
        next batch on."""
        return self.stages[2].prepare

    @prepare.setter
    def prepare(self, fn: Callable) -> None:
        self.stages[2].prepare = fn
