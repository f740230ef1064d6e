"""The Engine — one declarative entry point for every aggregation path.

``Engine(EngineConfig(...))`` (or ``Engine("ell+pipelined")``) resolves the
registered format and schedule once; ``engine.build(mesh)`` returns an
:class:`EngineBundle` — the compiled surface everything runs through:

    eng = Engine("ell+pipelined")
    bundle = eng.build(mesh)                       # mesh = the core axis
    batch = bundle.shard_batch(mb, feats, labels)  # host prep + placement
    params, loss = bundle.train_step(params, batch)
    y = bundle.aggregate(x, graph=coo)             # y = A @ x, distributed

The bundle owns the jit caches (one compiled step/forward per layer-dims
signature, one aggregator per graph), commits every batch leaf to its
core-axis sharding at build time (placement once per minibatch — the fix
for the measured re-layout-per-step regression), and derives ``shard_map``
specs from the batch pytree itself so any format's leaf structure works.

Single-device use needs no mesh: ``eng.layer(coo, x, w)`` runs the
format's GCN layer (layout built and cached per graph) with its
transpose-free backward.

``Engine("auto")`` defers the triple to :mod:`repro.engine.planner`:
:meth:`Engine.resolve` turns it into a concrete engine for a core count
(persisted autotune winner → fitted cost model → static fallback — pure
reads, no implicit sweep), and :meth:`Engine.build` resolves
automatically from the mesh's core count.  Resolution is cached per
(core count, stats bucket) so one auto engine resolves once.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.kernels.spmm import F32_MATMUL

from . import formats as _formats  # noqa: F401  (registers built-ins)
from .config import EngineConfig
from .registry import (Format, Schedule, get_format, get_schedule,
                       get_topology)

Dims = Tuple[Tuple[int, int], ...]


def _layout_cache_key(coo, *extra) -> tuple:
    from repro.kernels import edgeplan
    return edgeplan.coo_key(coo, "engine", *extra)


class Engine:
    """Resolved (format, schedule, topology) triple + the builders around
    them."""

    def __init__(self, config: Union[EngineConfig, str]):
        if isinstance(config, str):
            config = EngineConfig.from_spec(config)
        self.config: EngineConfig = config
        if config.is_auto:
            # deferred: the planner picks the triple at resolve/build time
            self.format = self.schedule = self.topology = None
            self._resolved: Dict[tuple, "Engine"] = {}
        else:
            self.format: Format = get_format(config.format)
            self.schedule: Schedule = get_schedule(config.schedule)
            self.topology = get_topology(config.topology)

    @property
    def spec(self) -> str:
        return self.config.spec

    @property
    def is_auto(self) -> bool:
        return self.config.is_auto

    @classmethod
    def available_specs(cls, *, three_part: bool = False) -> list:
        """Every spec ``Engine(...)`` accepts (the registry's canonical
        enumeration): two-part spellings plus ``"auto"`` by default, the
        concrete three-part product with ``three_part=True``."""
        from .registry import supported_specs
        return supported_specs(three_part=three_part)

    def resolve(self, n_cores: int, graph_stats=None) -> "Engine":
        """This engine with ``"auto"`` made concrete for ``n_cores``.

        Concrete engines return themselves; an auto engine asks the
        planner (:func:`repro.engine.planner.resolve_spec` — persisted
        winner → cost model → static fallback, never a sweep) and caches
        the result per (core count, stats bucket), carrying every knob of
        this config onto the resolved spec.
        """
        if not self.is_auto:
            return self
        from . import planner
        key = (int(n_cores),
               graph_stats.bucket() if graph_stats is not None else None)
        eng = self._resolved.get(key)
        if eng is None:
            spec = planner.resolve_spec(n_cores=int(n_cores),
                                        graph_stats=graph_stats)
            eng = Engine(self.config.with_spec(spec))
            self._resolved[key] = eng
        return eng

    # -- single-device layer ------------------------------------------------
    def layout(self, graph):
        """This format's single-device layout for ``graph`` (cached per COO
        identity when the graph is concrete; tracers build uncached)."""
        if self.is_auto:              # single-device: resolve at P=1
            return self.resolve(1).layout(graph)
        build = lambda: self.format.build_local(graph, self.config)  # noqa: E731
        if isinstance(graph.rows, jax.core.Tracer):
            if not self.format.traceable:
                raise ValueError(
                    f"format {self.config.format!r} builds its layout "
                    "host-side and cannot run on a traced graph (e.g. "
                    "inside jit over sampled COO layers); build the layout "
                    "outside the trace, or use a traceable format such as "
                    '"coo"')
            # inside a trace there is no stable identity to cache on
            return build()
        if not self.format.cache_layouts:
            return build()
        from repro.kernels import edgeplan
        key = _layout_cache_key(graph, self.config.format, self.config.caps,
                                self.config.block_tiles, self.config.merge)
        return edgeplan.cached(key, (graph.rows, graph.cols, graph.vals),
                               build)

    def layer(self, graph, x: jnp.ndarray, w: jnp.ndarray, *,
              order: str = "coag", activate: bool = True) -> jnp.ndarray:
        """Single-device GCN layer through this engine's format: layout
        build (cached), forward kernel, transpose-free backward."""
        if self.is_auto:
            return self.resolve(1).layer(graph, x, w, order=order,
                                         activate=activate)
        return self.format.layer(self.layout(graph), x, w, order=order,
                                 activate=activate)

    # -- distributed bundle --------------------------------------------------
    def build(self, mesh: Optional[Mesh] = None, *, graph=None,
              n_cores: Optional[int] = None) -> "EngineBundle":
        """Compile-ready bundle for ``mesh`` (``None`` + ``n_cores`` builds
        host-side shards without committing placement — single-process
        use).  ``graph`` pre-binds a default COO for ``aggregate``.  An
        explicit ``n_cores`` overrides the mesh-derived core count (shard
        shapes vs placement mesh — a mismatch fails loudly at step time).
        The bundle runs on ``mesh`` with its axes retyped ``Auto``
        (:func:`repro.distributed.sharding.auto_axes`), so the default
        ``jax.make_mesh`` mesh works outside any ``jax.set_mesh`` context.
        """
        if mesh is not None:
            from repro.distributed.sharding import auto_axes
            mesh = auto_axes(mesh)
        if n_cores is None:
            if mesh is None:
                raise ValueError("Engine.build needs a mesh or n_cores")
            n_cores = int(mesh.shape[self.config.axis])
        if self.is_auto:
            return self.resolve(n_cores).build(mesh, graph=graph,
                                               n_cores=n_cores)
        # the topology owns the core-count contract (every built-in needs a
        # power-of-two count — the block partitioning does too)
        self.topology.validate_cores(n_cores)
        return EngineBundle(engine=self, mesh=mesh, n_cores=n_cores,
                            graph=graph)


class EngineBundle:
    """Everything a training/benchmark loop calls, for one (engine, mesh).

    Public surface (the issue's contract): :meth:`train_step`,
    :meth:`forward`, :meth:`aggregate`, :meth:`shard_batch` — plus the
    explicit builders (:meth:`train_step_fn`, :meth:`forward_fn`,
    :meth:`aggregator`) when a caller wants the jitted callable itself.
    """

    def __init__(self, engine: Engine, mesh: Optional[Mesh],
                 n_cores: int, graph=None):
        self.engine = engine
        self.config = engine.config
        self.format = engine.format
        self.schedule = engine.schedule
        self.topology = engine.topology
        self.mesh = mesh
        self.n_cores = n_cores
        self.ndim = int(np.log2(n_cores))
        self.axis = self.config.axis
        self.graph = graph
        self.n_chunks = self.schedule.resolve_n_chunks(self.config.n_chunks)
        self._steps: Dict[Dims, Any] = {}
        self._forwards: Dict[Dims, Any] = {}

    @property
    def spec(self) -> str:
        """The CONCRETE spec this bundle compiled (auto is resolved by
        build time — a bundle never carries ``"auto"``)."""
        return self.config.spec

    # -- host-side batch prep ------------------------------------------------
    def prepare_batch(self, mb, features, labels: np.ndarray
                      ) -> Dict[str, Any]:
        """Sampled minibatch → HOST-side batch pytree (numpy leaves, no
        device placement).

        This is the expensive per-batch half — the format's layout build
        (``Format.prepare_batch``: edge sharding, block tiling, ELL plan
        construction) — and it is pure host work, safe to run on a prefetch
        thread so it overlaps the previous device step.  Feed the result to
        :meth:`commit_batch`; :meth:`shard_batch` composes the two for
        synchronous callers.

        ``features`` is either the gathered frontier rows (a dense
        ``[n_frontier, d]`` array) or an out-of-core source — a
        :class:`~repro.featurestore.FeatureStore` or
        :class:`~repro.featurestore.HotVertexCache` — in which case the
        frontier gather (``mb.input_nodes``, clamp-indexed like
        :func:`repro.data.gather_features`) happens HERE, store-side, so
        any shard_batch caller trains out-of-core with no other change."""
        if hasattr(features, "gather"):   # FeatureStore / HotVertexCache
            ids = np.minimum(np.asarray(mb.input_nodes, np.int64),
                             features.shape[0] - 1)
            features = features.gather(ids)
        features = np.asarray(features, np.float32)
        mb, features = self._apply_partition(mb, features)
        edges, dims = self.format.prepare_batch(mb, self.n_cores,
                                                self.config)
        labels = np.asarray(labels)
        if labels.ndim == 2:
            # multilabel rows → the dominant class, the single-label proxy
            # every engine train_step shares (BCE is a loss-layer variant,
            # not an aggregation-format concern)
            labels = labels.argmax(-1)
        return {
            "edges": edges,
            "dims": dims,
            "x": features,
            "labels": labels.astype(np.int32),
            "report": self._plan_report(mb, features.shape[-1]),
        }

    def _apply_partition(self, mb, features: np.ndarray):
        """``partition="mincom"``: relabel every non-batch node space with
        the communication-minimizing permutation chain
        (:func:`repro.graph.partition.mincom_layer_perms` — space 0 stays
        identity, so labels, logits and checkpointed batch order never
        move) and permute the frontier feature rows to match.  Cached on
        the layer chain's identity in the shared edge-plan LRU — repeated
        batches (and the aggregator path) pay the greedy passes once.
        ``naive`` (and a single-core mesh) returns the batch untouched."""
        if self.config.partition != "mincom" or self.n_cores <= 1:
            return mb, features
        from repro.graph.coo import from_edges
        from repro.graph.partition import mincom_layer_perms
        from repro.kernels import edgeplan

        layers = list(mb.layers)
        key = tuple(k for coo in layers for k in
                    edgeplan.coo_key(coo, "mincom-perms", self.n_cores))
        pins = tuple(a for coo in layers
                     for a in (coo.rows, coo.cols, coo.vals))
        perms = edgeplan.cached(
            key, pins, lambda: mincom_layer_perms(layers, self.n_cores))
        relabeled = [
            from_edges(perms[i][np.asarray(coo.rows, np.int64)],
                       perms[i + 1][np.asarray(coo.cols, np.int64)],
                       np.asarray(coo.vals, np.float32),
                       coo.n_dst, coo.n_src)
            for i, coo in enumerate(layers)]

        class _RelabeledMB:           # duck-typed: formats read .layers only
            layers = relabeled

        # frontier rows move with their space-L ids: new row perm[v] = old v
        x = features[np.argsort(perms[-1], kind="stable")]
        return _RelabeledMB(), x

    def _plan_report(self, mb, d: int) -> Dict[str, float]:
        """Host-side partition/merge observability for one prepared batch:
        measured exchange ``wire_bytes`` (per-core, summed over hop layers,
        post-merge row accounting through ``Topology.plan(wire_rows=...)``)
        plus the redundancy tier's ``virtual_vertices``/``pair_coverage``
        (ELL format only; the shard build is LRU-cached, so reading the
        stats here costs a cache hit)."""
        from repro.graph.partition import exchange_rows

        wire_bytes = 0
        for coo in mb.layers:
            wr = exchange_rows(np.asarray(coo.rows), np.asarray(coo.cols),
                               np.asarray(coo.vals), coo.n_dst, coo.n_src,
                               self.n_cores)
            wire_bytes += self.topology.plan(
                coo.n_dst, d, self.n_cores, wire_rows=wr).bytes_per_core
        report = {"wire_bytes": float(wire_bytes), "virtual_vertices": 0.0,
                  "pair_coverage": 0.0, "flop_reduction": 1.0}
        if self.config.format == "ell" and self.config.merge == "redundancy":
            from repro.distributed import aggregate as _agg
            nv = pu = eb = ea = 0.0
            for coo in mb.layers:
                ee = _agg.shard_edges_ell(coo, self.n_cores,
                                          caps=self.config.caps,
                                          merge=self.config.merge)
                nv += ee.n_virtual
                pu += ee.pair_coverage
                eb += ee.merge_stats.get("edges_before", 0)
                ea += ee.merge_stats.get("edges_after", 0)
            report["virtual_vertices"] = float(nv)
            report["pair_coverage"] = float(pu / max(len(mb.layers), 1))
            # aggregation MACs: every surviving edge is one, every virtual
            # vertex costs two (its z = alpha*x[u] + beta*x[v] build)
            report["flop_reduction"] = float(eb / max(ea + 2.0 * nv, 1.0))
        return report

    def commit_batch(self, host_batch: Dict[str, Any]) -> Dict[str, Any]:
        """Host batch (from :meth:`prepare_batch`) → device-ready arrays,
        every leaf committed to its core-axis
        :class:`~jax.sharding.NamedSharding` when the bundle has a mesh —
        placement happens once per minibatch, never per step (uncommitted
        arrays get re-laid-out by jit on EVERY step, the measured cause of
        a past ``agg_fwd_speedup < 1`` regression)."""
        if self.mesh is not None:
            from repro.distributed.sharding import leading_axis_put

            def put(a):
                return leading_axis_put(self.mesh, a, self.axis)
        else:
            put = jnp.asarray
        out = {
            "edges": [jax.tree_util.tree_map(put, leaves)
                      for leaves in host_batch["edges"]],
            "dims": host_batch["dims"],
            "x": put(host_batch["x"]),
            "labels": put(host_batch["labels"]),
        }
        if "report" in host_batch:
            # host-side observability floats — not a device leaf, and kept
            # out of the jitted step's pytree (train_step/forward pull
            # edges/x/labels explicitly)
            out["report"] = host_batch["report"]
        return out

    def shard_batch(self, mb, features: np.ndarray, labels: np.ndarray
                    ) -> Dict[str, Any]:
        """Sampled minibatch → device-ready sharded arrays.

        ``mb.layers`` are per-hop COOs deepest-first; ``features`` the
        frontier rows (padded to a multiple of P).  Synchronous composition
        of :meth:`prepare_batch` (host layout build) and
        :meth:`commit_batch` (one-time placement); async pipelines call the
        two halves from their producer thread instead."""
        return self.commit_batch(self.prepare_batch(mb, features, labels))

    # -- per-device forward (inside shard_map) -------------------------------
    def _forward_local(self, params, edges, dims: Dims, x_local):
        """2..L-layer GCN forward, deepest layer first (CoAg order): local
        combination matmul, then this format's aggregation under this
        schedule."""
        h = x_local
        n_layers = len(params)
        for l in range(n_layers - 1, -1, -1):
            n_dst, _ = dims[l]
            h = jnp.dot(h, params[n_layers - 1 - l]["w"],  # combination
                        precision=F32_MATMUL)
            h = self.format.device_aggregate(
                self.config.schedule, self.axis, self.ndim, n_dst,
                edges[l], h, self.n_chunks, topology=self.config.topology)
            if l != 0:
                h = jnp.maximum(h, 0.0)
        return h                                       # [batch/P, classes]

    def _shard_map(self, body, in_specs, out_specs):
        """``shard_map`` over the bundle's mesh, built when the caller
        traces it: inside a ``jax.set_mesh`` context of the same shape
        (e.g. the user's default ``Explicit`` mesh) the axes take the
        context's types, which ``shard_map`` requires to match; elsewhere
        they stay ``Auto``."""
        def call(*args):
            mesh = self.mesh
            ctx = jax.sharding.get_abstract_mesh()
            if not ctx.empty and ctx.shape_tuple == mesh.shape_tuple:
                mesh = mesh.update(axis_types=ctx.axis_types)
            return shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)(*args)
        return call

    def _require_mesh(self, what: str) -> Mesh:
        if self.mesh is None:
            raise ValueError(f"{what} needs a mesh — rebuild with "
                             "Engine.build(mesh)")
        return self.mesh

    def _edge_specs(self, edges):
        from repro.distributed.sharding import leading_axis_spec
        return jax.tree_util.tree_map(
            lambda a: leading_axis_spec(a, self.axis), edges)

    @staticmethod
    def _dims_key(dims) -> Dims:
        return tuple((int(a), int(b)) for a, b in dims)

    # -- training -------------------------------------------------------------
    def train_step_fn(self, dims: Sequence[Tuple[int, int]]):
        """Jitted ``step(params, batch) -> (params, loss)`` for fixed layer
        dims; params replicated, batch leaves sharded on their leading
        (core) axis.  Weight gradients are ``pmean``'d over the hypercube
        (the paper's Weight Bank sync) and applied with SGD at
        ``config.lr``."""
        dims = self._dims_key(dims)
        step = self._steps.get(dims)
        if step is not None:
            return step
        self._require_mesh("train_step")
        axis, lr = self.axis, self.config.lr

        def body(params, edges, x_local, labels_local):
            def loss_fn(params):
                logits = self._forward_local(params, edges, dims, x_local)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                nll = -jnp.take_along_axis(logp, labels_local[:, None],
                                           axis=-1)[:, 0]
                # mean over the GLOBAL batch (each core owns batch/P rows)
                return jax.lax.pmean(nll.mean(), axis)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            # Weight Bank sync: average weight grads over the hypercube
            grads = jax.lax.pmean(grads, axis)
            params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads)
            return params, loss

        def step(params, batch):
            fn = self._shard_map(
                body,
                in_specs=(P(), self._edge_specs(batch["edges"]),
                          P(axis, None), P(axis)),
                out_specs=(P(), P()))
            return fn(params, batch["edges"], batch["x"], batch["labels"])

        step = jax.jit(step)
        self._steps[dims] = step
        return step

    def train_step(self, params, batch):
        """``(params, loss) = step(params, batch)`` — compiled per the
        batch's layer-dims signature and cached on the bundle."""
        return self.train_step_fn(batch["dims"])(params, batch)

    # -- inference -------------------------------------------------------------
    def forward_fn(self, dims: Sequence[Tuple[int, int]]):
        """Jitted ``forward(params, batch) -> logits`` (global rows)."""
        dims = self._dims_key(dims)
        fwd = self._forwards.get(dims)
        if fwd is not None:
            return fwd
        self._require_mesh("forward")
        axis = self.axis

        def body(params, edges, x_local):
            return self._forward_local(params, edges, dims, x_local)

        def fwd(params, batch):
            fn = self._shard_map(
                body,
                in_specs=(P(), self._edge_specs(batch["edges"]),
                          P(axis, None)),
                out_specs=P(axis, None))
            return fn(params, batch["edges"], batch["x"])

        fwd = jax.jit(fwd)
        self._forwards[dims] = fwd
        return fwd

    def forward(self, params, batch):
        return self.forward_fn(batch["dims"])(params, batch)

    # -- raw distributed aggregation -------------------------------------------
    def aggregator(self, graph=None):
        """Jitted ``y = A @ x`` over the mesh for one COO: edge shards built
        host-side, committed to their core-axis sharding once, and closed
        over — the returned callable takes only the global ``x`` and is
        freely differentiable (the format's transpose-free backward).
        Cached per (graph identity, engine spec, mesh) in the shared
        ``edgeplan`` LRU, which pins the graph's arrays (and this mesh)
        alive so id reuse can never alias two graphs."""
        from repro.kernels import edgeplan

        coo = graph if graph is not None else self.graph
        if coo is None:
            raise ValueError("no graph: pass one to aggregator()/aggregate()"
                             " or to Engine.build(graph=...)")
        mesh = self._require_mesh("aggregate")
        key = _layout_cache_key(coo, "agg", self.config.spec, self.n_cores,
                                self.axis, self.config.caps, self.n_chunks,
                                self.config.merge, id(mesh))
        return edgeplan.cached(key, (coo.rows, coo.cols, coo.vals, mesh),
                               lambda: self._build_aggregator(coo, mesh))

    def _build_aggregator(self, coo, mesh: Mesh):
        from repro.distributed.sharding import leading_axis_put

        perm = None
        if self.config.partition == "mincom" and self.n_cores > 1 \
                and coo.n_dst == coo.n_src:
            # square one-space graph: one permutation relabels both sides;
            # x permutes in and y un-permutes out OUTSIDE shard_map (inside
            # the jit), so callers keep the original row order
            from repro.graph.coo import from_edges
            from repro.graph.partition import (mincom_assignment,
                                               partition_permutation)
            assign = mincom_assignment(np.asarray(coo.rows, np.int64),
                                       np.asarray(coo.cols, np.int64),
                                       coo.n_dst, self.n_cores)
            perm = partition_permutation(assign, self.n_cores)
            coo = from_edges(perm[np.asarray(coo.rows, np.int64)],
                             perm[np.asarray(coo.cols, np.int64)],
                             np.asarray(coo.vals, np.float32),
                             coo.n_dst, coo.n_src)

        leaves, n_dst, _ = self.format.shard(coo, self.n_cores, self.config)
        leaves = jax.tree_util.tree_map(
            lambda a: leading_axis_put(mesh, a, self.axis), leaves)

        def body(edge_leaves, x_local):
            return self.format.device_aggregate(
                self.config.schedule, self.axis, self.ndim, n_dst,
                edge_leaves, x_local, self.n_chunks,
                topology=self.config.topology)

        fn = self._shard_map(
            body, in_specs=(self._edge_specs(leaves), P(self.axis, None)),
            out_specs=P(self.axis, None))
        if perm is None:
            return jax.jit(lambda x: fn(leaves, x))
        to_new = jnp.asarray(np.argsort(perm, kind="stable"))
        to_old = jnp.asarray(perm)
        return jax.jit(
            lambda x: jnp.take(fn(leaves, jnp.take(x, to_new, axis=0)),
                               to_old, axis=0))

    def aggregate(self, x: jnp.ndarray, graph=None) -> jnp.ndarray:
        """``y = A @ x`` through this engine's format + schedule."""
        return self.aggregator(graph)(x)

    # -- single-device layer (delegates to the engine) --------------------------
    def layer(self, graph, x, w, *, order: str = "coag",
              activate: bool = True):
        return self.engine.layer(graph, x, w, order=order, activate=activate)
