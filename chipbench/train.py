"""Closed-loop sampled minibatch training: the traffic kind ``sampled_train``.

One :class:`TrainCell` is the system under test for a run: the program's
``Trainer`` on the configuration's graph, its batch stream replaced by a
:class:`Replay` of a fixed set of the epoch's batches.

Why a replay: every sampled batch has its own ELL bucket shapes, so every
new batch compiles its own step program.  The window may not compile, and
set-up must find every program in the persistent cache after a cell's first
run, whatever the seed.  So the traffic fixes ``distinct_batches`` batches
of the first epoch, drawn from its own ``batch_seed``; the run's seed draws
the order in which they are visited, and the initial weights.  Set-up runs
each of them once through the window's own call (``Trainer.train_steps``),
which compiles or loads every program; the window then goes round them
again and again.  Host work is real in every step: each visit samples,
gathers, lays out and places its batch anew.
"""
from __future__ import annotations

import time

import numpy as np


class CompileCounter:
    """Programs built (JAX's ``backend_compile_duration`` events, which a
    compile and a persistent-cache load alike emit), their seconds, and
    how many of them the persistent cache served."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.secs, self.hits


def structure(mb) -> dict:
    """What the sampler drew for one batch, on the host: per hop the
    (row, col) pairs (padding dropped) with the real destination and source
    counts, and the global ids of the deepest frontier, whose first ``n``
    entries are every shallower hop's nodes."""
    hops = []
    for l, coo in enumerate(mb.layers):
        real = np.asarray(coo.vals) != 0
        hops.append({"rows": np.asarray(coo.rows)[real],
                     "cols": np.asarray(coo.cols)[real],
                     "n_dst": int(mb.n_real[l]),
                     "n_src": int(mb.n_real[l + 1])})
    return {"hops": hops,
            "nodes": np.asarray(mb.input_nodes)[:mb.n_real[-1]],
            "n_roots": len(mb.seed_nodes)}


class Replay:
    """A batch source for the Trainer's prefetcher: the pipeline's
    first-epoch batches ``order[0], order[1], ...``, round and round.

    Follows the pipeline's restartable contract (``state``/``restore``), so
    the Trainer's own prefetcher drives it.  Records each batch's
    :func:`structure` the first time it is drawn (that is, in set-up), and
    the index of every batch drawn."""

    def __init__(self, pipeline, order, annotate: bool = False):
        self.pipeline = pipeline
        self.order = [int(i) for i in order]
        self.i = 0
        self.seen = {}
        self.annotate = annotate

    def __next__(self):
        idx = self.order[self.i % len(self.order)]
        self.pipeline.restore({"seed": self.pipeline.seed, "epoch": 0,
                               "batch_idx": idx})
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation("bench.sample"):
                item = next(self.pipeline)
        else:
            item = next(self.pipeline)
        if idx not in self.seen:
            self.seen[idx] = structure(item[0])
        self.i += 1
        return item

    def batch_at(self, step: int) -> dict:
        """The recorded structure of the ``step``-th batch drawn."""
        return self.seen[self.order[step % len(self.order)]]

    def state(self) -> dict:
        return {"i": self.i}

    def restore(self, state: dict) -> None:
        self.i = int(state.get("i", 0))


def seed_order(seed: int, n: int) -> list:
    return [int(i) for i in np.random.default_rng(int(seed)).permutation(n)]


class TrainCell:
    """The program's Trainer on the configuration's graph, driven as the
    traffic says; ``model`` is the configuration's model module
    (:func:`chipbench.run.load_model`).  ``step_hook(cell, params, batch)
    -> (params, loss)``, when given, takes the place of the engine's step
    (tests plant faults with it)."""

    def __init__(self, cfg: dict, traffic: dict, chips: int, data: dict,
                 model, annotate: bool = False, step_hook=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.engine.planner import DEFAULT_SPEC
        from repro.graph.datasets import DatasetStats, GraphDataset
        from repro.graph.sampler import CSRGraph
        from repro.launch.trainer import Trainer

        self.data, self.model, self.annotate = data, model, annotate
        if cfg["n_layers"] != len(traffic["fanouts"]):
            raise ValueError("one sampled hop per layer: n_layers "
                             f"{cfg['n_layers']} vs fanouts "
                             f"{traffic['fanouts']}")
        n = int(cfg["n_nodes"])
        stats = DatasetStats(cfg["dataset"], n, int(cfg["n_edges"]),
                             int(cfg["feat_dim"]), int(cfg["n_classes"]),
                             alpha=float(cfg["alpha"]))
        dataset = GraphDataset(
            stats=stats, graph=CSRGraph(data["indptr"], data["indices"], n),
            features=data["features"], labels=data["labels"], scale=1.0)
        self.trainer = tr = Trainer(
            DEFAULT_SPEC, dataset, n_cores=chips, hidden=cfg["hidden"],
            batch_size=traffic["batch_size"], fanouts=traffic["fanouts"],
            lr=cfg["lr"], seed=traffic["batch_seed"],
            prefetch_depth=traffic["prefetch_depth"],
            pad_multiple=traffic["pad_multiple"],
            **model.trainer_kwargs(cfg))
        self.lr = float(tr.engine.config.lr)
        self.replay = Replay(tr.pipeline, range(traffic["distinct_batches"]),
                             annotate)
        tr.fetcher.source = self.replay
        tr.fetcher.restore(self.replay.state())
        if annotate:
            prepare = tr.fetcher.prepare

            def annotated(*item):
                with jax.profiler.TraceAnnotation("bench.prepare"):
                    return prepare(*item)

            tr.fetcher.prepare = annotated
        if step_hook is not None:
            tr.bundle.train_step = lambda p, b: step_hook(self, p, b)
        self._init = jax.jit(
            lambda k: model.init(k, cfg),
            out_shardings=NamedSharding(tr.mesh, PartitionSpec()))
        self.devices = list(tr.mesh.devices.flat)

    def reseed(self, seed: int) -> None:
        """The seed's initial weights and batch order; the stream starts
        again from its first batch."""
        from chipbench import reference

        ws0 = self._init(reference.seed_words(seed))
        self.w0 = [np.asarray(w) for w in ws0]
        self.trainer.params = self.model.to_program(ws0)
        self.replay.order = seed_order(seed, len(self.replay.order))
        self.trainer.fetcher.restore({"i": 0})
        self.steps_done = 0

    def weights(self) -> list:
        return [np.asarray(w)
                for w in self.model.from_program(self.trainer.params)]

    def step(self) -> float:
        loss = self.trainer.train_steps(1)[0]
        self.steps_done += 1
        return loss

    def warm(self, compared: int) -> dict:
        """Set-up: one step on each of the distinct batches, in the seed's
        order; the first ``compared`` of them are what ``correct``
        compares.  Returns the program's side of that comparison."""
        out = {"losses": [], "w0": self.w0}
        for k in range(len(self.replay.order)):
            loss = self.step()
            if k < compared:
                out["losses"].append(loss)
            if k == 0:
                out["w1"] = self.weights()
            if k == compared - 1:
                out["w_last"] = self.weights()
        out["batches"] = [self.replay.batch_at(k) for k in range(compared)]
        return out

    def window(self, seconds: float) -> dict:
        """Whole steps until ``seconds`` have passed; the last one that
        started inside the window finishes."""
        import jax

        self.trainer.reset_stall_stats()
        first = self.steps_done
        losses = []
        t0 = time.perf_counter()
        while True:
            if self.annotate:
                with jax.profiler.StepTraceAnnotation(
                        "bench.step", step_num=self.steps_done):
                    losses.append(self.step())
            else:
                losses.append(self.step())
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        return {"seconds": t1 - t0, "losses": losses,
                "stall_s": self.trainer.stall_per_step,
                "batches": [self.replay.batch_at(k) for k in
                            range(first, self.steps_done)]}

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def close(self) -> None:
        self.trainer.close()
