"""Engine-native distributed Trainer — end-to-end training on EVERY
registered format×schedule spec, with host work off the critical path.

The paper's architecture wins by keeping the accelerator fed: NUMA-aware
host-side staging overlaps message-passing compute (§4.2–4.3).  This is
that split in software.  One :class:`Trainer` owns the whole loop:

  * **Engine-native** — the step is ``EngineBundle.train_step`` (shard_map
    over the hypercube axis, Weight-Bank ``pmean`` sync), so any registered
    spec trains unchanged: ``coo+serial``, ``block+pipelined``,
    ``ell+pipelined``, or a format you registered yesterday.
  * **Async input pipeline** — sampling, the feature gather, the
    per-batch layout build (``bundle.prepare_batch`` — the host-side hook
    that makes ``traceable=False`` formats trainable on sampled graphs)
    and device placement (``commit_batch``) run as a
    :class:`~repro.data.BatchChain`, each stage on its own thread with
    depth-2 queues between them, placement at most one batch ahead of the
    step; the step loop's only input cost is a queue pop.
    ``input_pipeline="sync"`` runs the same work inline for A/B
    measurement (``benchmarks/epoch_time.py --input-pipeline``).
  * **Epoch metrics** — per-epoch validation accuracy on a held-out seed
    set, wall-clock, steps/s and host-stall time per step.
  * **Checkpoint/resume** — params + progress counters + pipeline state
    via :class:`~repro.checkpoint.CheckpointManager`; the prefetcher drains
    and rewinds to the last consumed batch, so a mid-epoch restore replays
    the in-flight batches bit-exactly (the ``(seed, epoch, batch_idx)``
    contract).

CPU smoke (4 simulated cores)::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
        python -m repro.launch.trainer --spec ell+pipelined --n-cores 4 \\
        --steps 30 --ckpt-restart
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.gcn_paper import FANOUTS
from repro.data import BatchChain, GraphBatchPipeline, gather_features
from repro.distributed.gcn_train import init_params
from repro.engine import Engine, EngineConfig
from repro.featurestore import FeatureStore, HotVertexCache, get_store
from repro.graph import GraphDataset, NeighborSampler, make_dataset
from repro.launch import compile_cache


class Trainer:
    """One engine spec + one dataset → an epoch loop that trains it.

    Parameters
    ----------
    engine: spec string (``"ell+pipelined"``, or with an explicit
        interconnect ``"ell+pipelined+ring"``), :class:`EngineConfig`, or
        :class:`Engine` — every registered format×schedule×topology works.
    dataset: a :class:`GraphDataset` or a dataset name for
        :func:`make_dataset` (with ``scale``/``feat_dim``).
    n_cores: hypercube size; needs ``len(jax.devices()) >= n_cores``
        (``XLA_FLAGS=--xla_force_host_platform_device_count=P`` on CPU).
        ``mesh`` overrides with a prebuilt mesh.
    input_pipeline: ``"prefetch"`` (the staged chain sample | gather |
        layout | place, each stage on its own thread with depth
        ``prefetch_depth`` queues between them, placement at most one
        batch ahead of the step) or ``"sync"`` (host work inline on the
        step path — the A/B baseline).
    feature_store: where node features live.  ``None``/``"device"`` keeps
        them in a dense host array (unless the dataset itself is
        store-backed); a registered backend name (``"host"``, ``"mmap"``,
        …) wraps the dataset's dense features into that out-of-core
        store; a :class:`~repro.featurestore.FeatureStore` instance is
        used as-is.  The two modes differ only in where the gather stage
        reads; either way only each batch's frontier rows reach the
        device.
    cache_capacity: rows in the degree-keyed hot-vertex cache in front of
        the store (0 disables); ``cache_pinned`` of them pin the
        top-degree vertices (default: half), the rest are LRU.
    device_budget_bytes: simulated per-device feature-memory budget — a
        DENSE feature matrix over this size refuses to train (pass a
        ``feature_store`` instead); store-backed features are exempt, as
        only frontier rows ever occupy device memory.
    pad_multiple: sampler node-count padding.  Coarser padding collapses
        the per-batch ``dims`` signatures so the jitted step re-traces
        rarely; must be a multiple of ``n_cores`` (defaults to
        ``max(16, n_cores)``).
    ckpt_every: save (async) every N global steps when ``ckpt_dir`` is set.
    """

    def __init__(self, engine: Union[str, EngineConfig, Engine],
                 dataset: Union[str, GraphDataset] = "flickr", *,
                 n_cores: int = 1, mesh=None, scale: float = 0.01,
                 feat_dim: Optional[int] = None, hidden: int = 64,
                 batch_size: int = 64, fanouts: Sequence[int] = FANOUTS,
                 lr: Optional[float] = None, seed: int = 0,
                 input_pipeline: str = "prefetch", prefetch_depth: int = 2,
                 pad_multiple: Optional[int] = None,
                 val_batches: int = 2,
                 feature_store: Union[None, str, FeatureStore] = None,
                 cache_capacity: int = 0,
                 cache_pinned: Optional[int] = None,
                 device_budget_bytes: Optional[int] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 log_every: int = 0):
        if input_pipeline not in ("prefetch", "sync"):
            raise ValueError(f"unknown input_pipeline {input_pipeline!r}; "
                             "expected 'prefetch' or 'sync'")
        if isinstance(engine, Engine):
            if lr is not None and lr != engine.config.lr:
                raise ValueError(
                    f"lr={lr} conflicts with the prebuilt Engine's "
                    f"config.lr={engine.config.lr} (the step bakes the "
                    "engine's lr in) — pass a spec/EngineConfig, or set "
                    "the lr on the EngineConfig you build the Engine from")
        else:
            if isinstance(engine, str):
                engine = EngineConfig.from_spec(
                    engine, **({} if lr is None else {"lr": lr}))
            elif lr is not None:
                engine = EngineConfig(**{**engine.__dict__, "lr": lr})
            engine = Engine(engine)
        self.requested_spec = engine.spec
        if engine.is_auto:
            # resolve BEFORE any mesh exists: the topology-aware mesh needs
            # a concrete interconnect, and a run plans exactly once —
            # resume pins this resolved spec, never re-plans mid-run
            engine = engine.resolve(
                int(mesh.shape[engine.config.axis]) if mesh is not None
                else n_cores)
        self.engine = engine
        if isinstance(dataset, str):
            dataset = make_dataset(dataset, scale=scale, feat_dim=feat_dim)
        self.dataset = dataset
        # -- feature residency: dense on-device vs out-of-core store ---------
        self._owned_store = False
        feats = dataset.features
        store: Optional[FeatureStore] = None
        if isinstance(feature_store, FeatureStore):
            store = feature_store
        elif isinstance(feats, FeatureStore):
            # the dataset was generated out-of-core — train from its store
            # regardless of the flag (densifying it would defeat the point)
            store = feats
        elif feature_store not in (None, "device"):
            # wrap the dense matrix into the named backend through the
            # chunked writer (mmap streams it to disk chunk by chunk)
            store = get_store(feature_store).from_array(np.asarray(feats))
            self._owned_store = True
        if device_budget_bytes is not None and store is None \
                and feats.nbytes > device_budget_bytes:
            raise ValueError(
                f"dense features are {feats.nbytes} bytes — over the "
                f"device_budget_bytes={device_budget_bytes} budget; pass "
                "feature_store='host' or 'mmap' so only each batch's "
                "frontier rows ever occupy device memory")
        self.store = store
        self.cache: Optional[HotVertexCache] = None
        if store is not None and cache_capacity > 0:
            indptr = dataset.graph.indptr
            self.cache = HotVertexCache(store, indptr[1:] - indptr[:-1],
                                        cache_capacity, pinned=cache_pinned)
        self._gather_src = self.cache if self.cache is not None \
            else store if store is not None else feats
        self.feature_mode = "device" if store is None \
            else getattr(store, "name", "custom")
        if mesh is None:
            # topology-aware construction: the engine's interconnect
            # validates the core count before any device state is touched
            from repro.launch.mesh import make_topology_mesh
            mesh = make_topology_mesh(n_cores, engine.config.topology,
                                      engine.config.axis)
        self.mesh = mesh
        self.n_cores = int(mesh.shape[engine.config.axis])
        self.bundle = engine.build(mesh)
        self.batch_size = batch_size
        self.seed = seed
        self.input_pipeline = input_pipeline
        self.log_every = log_every
        pad = pad_multiple if pad_multiple is not None \
            else max(16, self.n_cores)
        if pad % self.n_cores:
            raise ValueError(f"pad_multiple={pad} must be a multiple of "
                             f"n_cores={self.n_cores} so every hop splits "
                             "evenly across the hypercube")
        if dataset.graph.n_nodes < batch_size:
            raise ValueError(
                f"batch_size={batch_size} exceeds the dataset's "
                f"{dataset.graph.n_nodes} nodes — an epoch would hold zero "
                "full batches and fit() would train nothing; shrink the "
                "batch or raise the dataset scale")
        self.sampler = NeighborSampler(dataset.graph, fanouts=fanouts,
                                       pad_multiple=pad, seed=seed)
        # the gather is always its own stage (sync mode calls it inline)
        self.pipeline = GraphBatchPipeline(dataset, self.sampler,
                                           batch_size, seed=seed,
                                           defer_gather=True)
        self._nnz_pad = self.sampler.static_nnz(batch_size)
        self.fetcher: Optional[BatchChain] = None
        if input_pipeline == "prefetch":
            # the bundle is looked up per batch: a resume may rebind it
            self.fetcher = BatchChain(
                self.pipeline, self._gather_stage,
                lambda *item: self.bundle.prepare_batch(*item),
                lambda host: self.bundle.commit_batch(host),
                depth=prefetch_depth)
        # model: one GCN layer per sampled hop, hidden width between
        feat = dataset.features.shape[1]
        dims = [feat] + [hidden] * (len(fanouts) - 1) \
            + [dataset.stats.n_classes]
        self.params = init_params(jax.random.PRNGKey(seed),
                                  list(zip(dims[:-1], dims[1:])))
        self.mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.global_step = 0
        self.epochs_done = 0
        # held-out validation seeds: derived from the seed, never from the
        # training stream — identical across resume boundaries
        val_rng = np.random.default_rng(
            np.random.SeedSequence([seed, 9001]))
        self._val_seed_sets = [
            val_rng.permutation(dataset.graph.n_nodes)[:batch_size]
            for _ in range(val_batches)]
        self._val_batches: Optional[List[Any]] = None
        self.history: List[float] = []
        self._sync_stall_s = 0.0
        self._sync_steps = 0

    # -- input pipeline ------------------------------------------------------
    def _prepare(self, mb, feats, labels) -> Dict[str, Any]:
        """Host layout build → device placement, inline: the sync path
        and the validation batches."""
        return self.bundle.commit_batch(
            self.bundle.prepare_batch(mb, feats, labels))

    def _gather_stage(self, mb, labels):
        """The gather stage: frontier rows out of the dense features or
        the feature store, through the hot-vertex cache when one is
        enabled."""
        feats = gather_features(self._gather_src, mb.input_nodes,
                                self.dataset.graph.n_nodes, mb.batch)
        return mb, feats, labels

    def _next_batch(self) -> Dict[str, Any]:
        if self.fetcher is not None:
            return next(self.fetcher)
        t0 = time.perf_counter()
        batch = self._prepare(*self._gather_stage(*next(self.pipeline)))
        self._sync_stall_s += time.perf_counter() - t0
        self._sync_steps += 1
        return batch

    @property
    def stall_per_step(self) -> float:
        """Host time the device step could not hide, per consumed batch."""
        if self.fetcher is not None:
            return self.fetcher.stall_per_step
        return self._sync_stall_s / max(self._sync_steps, 1)

    def reset_stall_stats(self) -> None:
        if self.fetcher is not None:
            self.fetcher.reset_stats()
        self._sync_stall_s = 0.0
        self._sync_steps = 0

    # -- checkpoint/resume ---------------------------------------------------
    def _pipeline_state(self) -> Dict[str, int]:
        return self.fetcher.state() if self.fetcher is not None \
            else self.pipeline.state()

    def _extra(self) -> Dict[str, Any]:
        return {"step": self.global_step, "epochs_done": self.epochs_done,
                "pipeline": self._pipeline_state(),
                "spec": self.engine.spec,
                "requested_spec": self.requested_spec}

    def save(self, *, sync: bool = False) -> None:
        if self.mgr is None:
            return
        fn = self.mgr.save if sync else self.mgr.save_async
        fn(self.global_step, self.params, extra=self._extra())

    def resume(self) -> bool:
        """Restore the newest checkpoint (params + progress + the exact
        next-batch position).  Returns False when none exists."""
        if self.mgr is None:
            return False
        hit = self.mgr.restore_latest(self.params)
        if hit is None:
            return False
        self.params, extra, _ = hit
        saved_spec = extra.get("spec")
        if self.requested_spec == "auto" and saved_spec \
                and saved_spec != self.engine.spec:
            # the checkpoint pins the concrete spec its auto run resolved
            # at launch — a resume must continue bit-exactly on those
            # wires even if the planner record changed since
            self._rebind(saved_spec)
        self.global_step = int(extra["step"])
        self.epochs_done = int(extra.get("epochs_done", 0))
        if self.fetcher is not None:
            self.fetcher.restore(extra["pipeline"])
        else:
            self.pipeline.restore(extra["pipeline"])
        return True

    def _rebind(self, spec: str) -> None:
        """Swap the concrete engine under the existing mesh (the mesh is
        1-D for every topology, so only the bundle rebuilds); cached val
        batches are invalidated — they were placed through the old
        bundle."""
        engine = Engine(self.engine.config.with_spec(spec))
        engine.topology.validate_cores(self.n_cores)
        self.engine = engine
        self.bundle = engine.build(self.mesh)
        self._val_batches = None

    def close(self) -> None:
        if self.fetcher is not None:
            self.fetcher.close()
        if self._owned_store and self.store is not None:
            # only stores the Trainer created (from_array wrapping) are
            # closed here — a dataset-owned or caller-passed store may be
            # shared and outlives this Trainer
            self.store.close()
        if self.mgr is not None:
            self.mgr.wait()

    # -- the loop ------------------------------------------------------------
    def train_steps(self, n_steps: int) -> List[float]:
        """Run ``n_steps`` optimizer steps; returns their losses."""
        losses: List[float] = []
        for _ in range(n_steps):
            # no reference to the batch outlives its step: the chain places
            # the next one while this one runs
            self.params, loss = self.bundle.train_step(
                self.params, self._next_batch())
            losses.append(float(loss))
            self.global_step += 1
            if self.log_every and self.global_step % self.log_every == 0:
                print(f"step {self.global_step:5d}  loss "
                      f"{losses[-1]:.4f}  stall/step "
                      f"{self.stall_per_step * 1e3:.1f} ms")
            if self.mgr and self.ckpt_every \
                    and self.global_step % self.ckpt_every == 0:
                self.save()
        self.history.extend(losses)
        return losses

    def _build_val_batches(self) -> List[Any]:
        """The val batches are deterministic (seed sets + per-batch rngs
        fixed at construction), so they are sampled, laid out, and placed
        ONCE and reused every epoch — re-preparing them would redo the
        layout builds per epoch and churn the shared plan cache for
        byte-identical results."""
        from repro.data import assemble_batch

        batches = []
        for seeds in self._val_seed_sets:
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, 7, int(seeds[0])]))
            # the SAME assembly rule as the training pipeline, so the val
            # path can never drift from what the train step consumes
            mb, feats, labels = assemble_batch(self.dataset, self.sampler,
                                               seeds, self._nnz_pad, rng)
            batches.append((len(seeds), self._prepare(mb, feats, labels)))
        return batches

    def evaluate(self) -> float:
        """Validation accuracy on the held-out seed sets (padded rows
        masked host-side; multilabel datasets score the argmax proxy, same
        target the train step optimizes)."""
        if self._val_batches is None:
            self._val_batches = self._build_val_batches()
        hits = total = 0
        for n_seeds, batch in self._val_batches:
            logits = np.asarray(self.bundle.forward(self.params, batch))
            want = np.asarray(batch["labels"])[:n_seeds]
            hits += int((logits[:n_seeds].argmax(-1) == want).sum())
            total += n_seeds
        return hits / max(total, 1)

    def fit(self, epochs: int = 1, *, steps_per_epoch: Optional[int] = None,
            max_steps: Optional[int] = None, resume: bool = False
            ) -> Dict[str, Any]:
        """Epoch loop: train → validate → record metrics (+ checkpoint).

        ``steps_per_epoch`` defaults to the dataset's full epoch;
        ``max_steps`` caps the TOTAL (global) step count, so a resumed run
        continues to the same horizon as an uninterrupted one.
        """
        if resume:
            self.resume()
        spe = steps_per_epoch if steps_per_epoch is not None \
            else self.pipeline.batches_per_epoch
        out: Dict[str, Any] = {"spec": self.engine.spec,
                               "requested_spec": self.requested_spec,
                               "n_cores": self.n_cores,
                               "input_pipeline": self.input_pipeline,
                               "feature_store": self.feature_mode,
                               "loss_history": [], "val_acc": [],
                               "epoch_s": [], "steps_per_s": [],
                               "host_stall_s_per_step": []}
        t_all = time.time()
        try:
            for _ in range(self.epochs_done, epochs):
                budget = spe
                if max_steps is not None:
                    budget = min(budget, max_steps - self.global_step)
                if budget <= 0:
                    break
                self.reset_stall_stats()
                t0 = time.time()
                losses = self.train_steps(budget)
                dt = time.time() - t0
                out["loss_history"].extend(losses)
                out["epoch_s"].append(dt)
                out["steps_per_s"].append(len(losses) / max(dt, 1e-9))
                out["host_stall_s_per_step"].append(self.stall_per_step)
                out["val_acc"].append(self.evaluate())
                self.epochs_done += 1
                if self.log_every:
                    print(f"epoch {self.epochs_done}: loss "
                          f"{losses[-1]:.4f}  val_acc "
                          f"{out['val_acc'][-1]:.3f}  "
                          f"{out['steps_per_s'][-1]:.1f} steps/s  "
                          f"stall/step "
                          f"{out['host_stall_s_per_step'][-1] * 1e3:.1f} ms")
                if self.mgr is not None:
                    self.save()
        finally:
            self.close()
        out["wall_s"] = time.time() - t_all
        out["global_step"] = self.global_step
        out["params"] = self.params
        if self.store is not None:
            out["gather_calls"] = int(self.store.gather_calls)
            out["gather_bytes"] = int(self.store.bytes_gathered)
            if self.cache is not None:
                out["cache"] = self.cache.stats()
        if self.fetcher is not None:
            # last epoch's per-stage stalls (stage k's stall = time it
            # waited on stage k-1 — where the chain is bottlenecked)
            out["stage_stall_s_per_step"] = self.fetcher.stage_stalls()
        return out


# ---------------------------------------------------------------------------
# CLI — the CI trainer smoke: train, checkpoint mid-run, restart, resume.
# ---------------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spec", default="ell+pipelined",
                    help="engine spec (repro.engine.supported_specs())")
    ap.add_argument("--dataset", default="flickr")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--feat-dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--n-cores", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--input-pipeline", default="prefetch",
                    choices=["prefetch", "sync"])
    ap.add_argument("--feature-store", default="device",
                    help="'device' (dense in-memory features, the default)"
                         " or a registered featurestore backend ('host', "
                         "'mmap') to stream frontier rows out-of-core")
    ap.add_argument("--cache-capacity", type=int, default=0,
                    help="hot-vertex cache rows in front of the store "
                         "(0 disables; needs --feature-store)")
    ap.add_argument("--cache-pinned", type=int, default=None,
                    help="cache rows pinned to the top-degree vertices "
                         "(default: half the capacity)")
    ap.add_argument("--pad-multiple", type=int, default=None,
                    help="coarser sampler padding → fewer distinct dims "
                         "signatures → fewer jit re-traces")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-restart", action="store_true",
                    help="smoke the fault path: checkpoint at the midpoint,"
                         " rebuild the Trainer, resume, and assert the "
                         "resumed trajectory matches an uninterrupted run")
    args = ap.parse_args(argv)
    compile_cache.enable()

    def build(pipeline: str, ckpt: Optional[str]) -> Trainer:
        fs = None if args.feature_store == "device" else args.feature_store
        return Trainer(args.spec, args.dataset, n_cores=args.n_cores,
                       scale=args.scale, feat_dim=args.feat_dim,
                       hidden=args.hidden, batch_size=args.batch_size,
                       lr=args.lr, seed=args.seed, input_pipeline=pipeline,
                       pad_multiple=args.pad_multiple,
                       feature_store=fs, cache_capacity=args.cache_capacity,
                       cache_pinned=args.cache_pinned,
                       ckpt_dir=ckpt, ckpt_every=0, log_every=10)

    if args.ckpt_restart:
        import tempfile
        mid = args.steps // 2
        with tempfile.TemporaryDirectory() as ckpt:
            full = build(args.input_pipeline, None)
            ref = full.fit(1, steps_per_epoch=args.steps)
            part = build(args.input_pipeline, ckpt)
            part.train_steps(mid)
            part.save(sync=True)
            part.close()
            resumed = build(args.input_pipeline, ckpt)
            out = resumed.fit(1, steps_per_epoch=args.steps - mid,
                              resume=True)
        drift = max(abs(a - b) for a, b in
                    zip(ref["loss_history"][mid:], out["loss_history"]))
        print(f"resume drift vs uninterrupted: {drift:.2e}")
        assert drift <= 1e-6, drift
        cache = out.get("cache")
        extra = (f"  store={out['feature_store']} "
                 f"cache_hit_rate={cache['hit_rate']:.2f}"
                 if cache else f"  store={out['feature_store']}")
        print(f"OK spec={args.spec} cores={args.n_cores} "
              f"steps={args.steps} (ckpt@{mid} + resume, batch-exact)  "
              f"val_acc={out['val_acc'][-1]:.3f}{extra}")
        return

    tr = build(args.input_pipeline, args.ckpt_dir)
    out = tr.fit(1, steps_per_epoch=args.steps, resume=args.resume)
    print(f"final loss {out['loss_history'][-1]:.4f}  val_acc "
          f"{out['val_acc'][-1]:.3f}  {out['steps_per_s'][-1]:.1f} steps/s "
          f"({out['wall_s']:.1f}s, stall/step "
          f"{out['host_stall_s_per_step'][-1] * 1e3:.1f} ms)")


if __name__ == "__main__":
    main()
