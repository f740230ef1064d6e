"""Profile-guided spec planner — what ``Engine("auto")`` resolves through.

The paper's claim is that the interconnect schedule, not just the kernel,
decides training throughput; PR 5 made the topology a declarative axis so
specs could be compared, and this module stops hand-picking them.  An
``"auto"`` spec resolves to a concrete ``format+schedule+topology`` before
anything compiles, through three tiers:

1. **Persisted autotune winner** — :func:`autotune` times every candidate
   spec bundle on the actual backend (the paired-median methodology of
   ``benchmarks/epoch_time.py``, re-execing itself under
   ``XLA_FLAGS=--xla_force_host_platform_device_count`` when the process
   has too few devices) and persists the winner per
   ``(backend, n_cores, graph-stats bucket)`` to ``BENCH_planner.json``.
   A matching entry is the strongest evidence and wins outright.
2. **Analytic cost model** — :func:`fit_cost_model` fits nonnegative
   ``t = const + α·steps + β·effective_bytes`` coefficients against the
   per-topology step times recorded in ``BENCH_topology.json``
   (``effective_bytes = bytes_per_core / link_parallelism`` — torus2d's
   orthogonal halves keep two link sets busy).  :func:`rank_specs` scores
   every candidate's :class:`~repro.topology.base.ExchangePlan` with it,
   scaling the compute-side ``const`` term by per-format roofline seconds
   from :mod:`repro.launch.hlo_analysis` when graph stats are given.
3. **Static fallback** — :data:`DEFAULT_SPEC` (``ell+pipelined+hypercube``,
   the measured best).  No file, no fit, no devices → still a valid spec,
   with no implicit sweep at import or test time.

Both stores ride the shared :class:`repro.engine.plans.RecordStore`
contract (explicit path → ``$REPRO_PLANNER_PATH`` / ``$REPRO_TOPOLOGY_PATH``
→ default filename in the CWD); corrupt or stale records warn and fall
through, they never crash a training run.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import sys
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from .plans import RecordStore
from .registry import supported_specs

#: the measured-best static fallback (tier 3) — the paper's format and NoC
DEFAULT_SPEC = "ell+pipelined+hypercube"

#: autotune winners, keyed ``"{backend}|P{n_cores}|{bucket}"``
PLANNER_STORE = RecordStore("BENCH_planner.json", "REPRO_PLANNER_PATH")
#: the topology sweep record the cost model fits against
TOPOLOGY_STORE = RecordStore("BENCH_topology.json", "REPRO_TOPOLOGY_PATH")


def _pow2(v: float) -> int:
    """Round up to the next power of two (bucket resolution)."""
    n = max(int(-(-v // 1)), 1)              # ceil without math import
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class GraphStats:
    """The workload coordinates a plan is keyed on.

    ``n_dst``/``n_src`` are the deepest sampled layer's destination/source
    row counts (the rows the exchange actually ships), ``avg_deg`` its
    average in-degree, ``feat_dim`` the feature width.  :meth:`bucket`
    rounds each up to a power of two so nearby workloads share one
    autotune record instead of sweeping per batch.
    """

    n_dst: int
    n_src: int
    avg_deg: float
    feat_dim: int

    @classmethod
    def from_layers(cls, layers, feat_dim: int) -> "GraphStats":
        """Stats of the deepest (widest-frontier) COO layer in ``layers``."""
        deepest = max(layers, key=lambda c: c.n_src)
        return cls(n_dst=int(deepest.n_dst), n_src=int(deepest.n_src),
                   avg_deg=float(deepest.nnz) / max(int(deepest.n_dst), 1),
                   feat_dim=int(feat_dim))

    def bucket(self) -> str:
        return (f"n{_pow2(self.n_dst)}_s{_pow2(self.n_src)}"
                f"_d{_pow2(self.avg_deg)}_f{_pow2(self.feat_dim)}")


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Fitted ``t = const + α·steps + β·effective_bytes`` (all ≥ 0).

    Nonnegative coefficients make the prediction monotone by construction:
    more steps or more wire bytes can never predict a faster exchange.
    ``n_rows``/``d``/``base_spec`` record the workload the fit came from so
    :func:`rank_specs` can re-plan candidates at the same coordinates.
    """

    alpha: float                  # seconds per exchange step (latency)
    beta: float                   # seconds per effective wire byte
    const: float                  # exchange-independent step time
    n_cores: int
    backend: Optional[str] = None
    base_spec: str = "ell+pipelined"
    n_rows: int = 512
    d: int = 128
    source: str = "fit"

    def predict(self, plan) -> float:
        """Predicted seconds per train step under ``plan``."""
        eff = plan.bytes_per_core / max(
            getattr(plan, "link_parallelism", 1.0), 1.0)
        return self.const + self.alpha * plan.steps + self.beta * eff


def _nnls(rows: Sequence[Sequence[float]], y: Sequence[float]):
    """Nonnegative least squares via active-set clamping.

    Solve the normalized LS problem, drop the most-negative column, repeat;
    dropped coefficients are exactly zero.  Small (3-column) systems only —
    the clamp is what guarantees the cost model's monotonicity.
    """
    import numpy as np

    A = np.asarray(rows, dtype=float)
    y = np.asarray(y, dtype=float)
    norms = np.linalg.norm(A, axis=0)
    norms[norms == 0] = 1.0
    An = A / norms
    active = list(range(A.shape[1]))
    coef = np.zeros(A.shape[1])
    while active:
        sol, *_ = np.linalg.lstsq(An[:, active], y, rcond=None)
        if (sol >= -1e-12).all():
            for i, c in zip(active, sol):
                coef[i] = max(float(c), 0.0)
            break
        active.pop(int(np.argmin(sol)))
    return coef / norms


def _backend() -> str:
    import jax
    return jax.default_backend()


def _record_link_parallelism(record: Dict, topo: str) -> float:
    """link_parallelism for ``topo``: the record's own column when present
    (new sweeps write it), else the registered topology, else 1.0."""
    v = record.get(f"link_parallelism_{topo}")
    if v is not None:
        return float(v)
    from .registry import get_topology
    try:
        return float(get_topology(topo).link_parallelism)
    except ValueError:
        return 1.0


def fit_cost_model(record: Optional[Dict] = None, *,
                   n_cores: Optional[int] = None,
                   backend: Optional[str] = None,
                   path: Optional[str] = None) -> Optional[CostModel]:
    """Fit α/β/const against a ``BENCH_topology.json`` sweep record.

    ``record=None`` loads the topology store (file → ``$REPRO_TOPOLOGY_PATH``
    → CWD default).  Returns ``None`` — never raises — when there is no
    usable record: missing/corrupt file, an ``n_cores`` or ``backend``
    mismatch (coefficients are per-(backend, axis-size); a 4-core sweep says
    nothing about a 2-core mesh), or fewer than 3 measured arms (the fit
    has 3 unknowns).
    """
    if record is None:
        record = TOPOLOGY_STORE.load(path, warn_corrupt=True)
    if not isinstance(record, dict):
        return None
    if n_cores is not None and record.get("n_cores") != n_cores:
        return None
    rec_backend = record.get("backend")
    if backend is not None and rec_backend is not None \
            and rec_backend != backend:
        return None
    rows, y = [], []
    for topo in record.get("topologies") or []:
        steps = record.get(f"exchange_steps_{topo}")
        nbytes = record.get(f"exchange_bytes_per_core_{topo}")
        t = record.get(f"s_per_step_{topo}")
        if steps is None or nbytes is None or t is None:
            continue
        eff = float(nbytes) / max(_record_link_parallelism(record, topo),
                                  1.0)
        rows.append([1.0, float(steps), eff])
        y.append(float(t))
    if len(rows) < 3:
        return None
    const, alpha, beta = _nnls(rows, y)
    return CostModel(alpha=float(alpha), beta=float(beta),
                     const=float(const),
                     n_cores=int(record.get("n_cores", n_cores or 0)),
                     backend=rec_backend,
                     base_spec=record.get("base_spec", "ell+pipelined"),
                     n_rows=int(record.get("mid", 512)),
                     d=int(record.get("feat", 128)))


# ---------------------------------------------------------------------------
# Format-side compute estimate: roofline seconds of the compiled
# single-device layer, per (backend, format+schedule, size bucket).
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _format_roofline_seconds(backend: str, fmt_spec: str,
                             dims: Tuple[int, int, int, int]
                             ) -> Optional[float]:
    """t_compute + t_memory of one compiled layer (None on any failure —
    a format that will not compile here just keeps ratio 1.0)."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.graph.coo import from_edges
        from repro.launch.hlo_analysis import analyze_hlo, roofline_terms

        from .config import EngineConfig
        from .registry import get_format

        n_dst, n_src, deg, d = dims
        cfg = EngineConfig.from_spec(fmt_spec)
        fmt = get_format(cfg.format)
        rng = np.random.default_rng(0)
        e = n_dst * deg
        coo = from_edges(rng.integers(0, n_dst, e),
                         rng.integers(0, n_src, e),
                         np.abs(rng.standard_normal(e))
                         .astype(np.float32) + 0.1, n_dst, n_src)
        layout = fmt.build_local(coo, cfg)
        x = jnp.zeros((n_src, d), jnp.float32)
        w = jnp.zeros((d, d), jnp.float32)
        txt = jax.jit(lambda x, w: fmt.layer(layout, x, w)) \
            .lower(x, w).compile().as_text()
        stats = analyze_hlo(txt, 1)
        terms = roofline_terms(stats.flops, stats.hbm_bytes,
                               stats.collective_wire_bytes, 1)
        return terms["t_compute"] + terms["t_memory"]
    except Exception as e:                    # noqa: BLE001 — estimate only
        warnings.warn(f"no roofline estimate for {fmt_spec!r}: {e}",
                      RuntimeWarning, stacklevel=2)
        return None


def _roofline_dims(stats: GraphStats) -> Tuple[int, int, int, int]:
    # capped: the ratio between formats stabilizes long before real sizes
    return (min(_pow2(stats.n_dst), 512), min(_pow2(stats.n_src), 1024),
            min(_pow2(stats.avg_deg), 16), min(_pow2(stats.feat_dim), 128))


def rank_specs(model: CostModel, n_cores: int, *,
               graph_stats: Optional[GraphStats] = None,
               backend: Optional[str] = None,
               candidates: Optional[Sequence[str]] = None,
               mode: str = "train", max_batch: int = 8
               ) -> List[Tuple[str, float]]:
    """Candidate three-part specs sorted by predicted seconds.

    The exchange side scores each topology's :class:`ExchangePlan` through
    ``model``; the compute side scales ``model.const`` by the candidate
    format's roofline seconds relative to the fitted base format (only when
    ``graph_stats`` pins a workload — without one every format scores 1.0
    and the ranking is purely the interconnect).  Ties prefer
    ``ell+pipelined`` (the measured-best format arm), then lexicographic —
    deterministic, so resumes re-rank identically.

    ``mode`` picks the objective:

    * ``"train"`` — per-step seconds at the fitted workload's row count
      (throughput: the bytes term dominates at training batch sizes).
    * ``"serving"`` — mean predicted LATENCY over coalesced micro-batch
      sizes ``1, 2, 4, … max_batch``.  Online rows-per-exchange are tiny,
      so the per-step α·steps latency term dominates and the ranking can
      invert relative to train mode — a topology that wins on wire bytes
      at 512 rows loses at 4 rows if it takes more hops.  Every batch size
      weighs equally (each micro-batch is one user-visible latency, not
      one row).
    """
    from .registry import get_topology

    if mode not in ("train", "serving"):
        raise ValueError(f"unknown rank mode {mode!r}; "
                         "expected 'train' or 'serving'")
    specs = list(candidates) if candidates is not None \
        else supported_specs(three_part=True)
    n_rows = graph_stats.n_dst if graph_stats is not None else model.n_rows
    d = graph_stats.feat_dim if graph_stats is not None else model.d
    if mode == "serving":
        batch_sizes = []
        b = 1
        while b < max_batch:
            batch_sizes.append(b)
            b *= 2
        batch_sizes.append(max_batch)
    else:
        batch_sizes = [n_rows]
    base_s = None
    if graph_stats is not None:
        backend = backend or _backend()
        dims = _roofline_dims(graph_stats)
        base_s = _format_roofline_seconds(backend, model.base_spec, dims)
    scored = []
    for spec in specs:
        fmt, sched, topo = spec.split("+")
        try:
            plans = [get_topology(topo).plan(b, d, n_cores,
                                             cost_model=model)
                     for b in batch_sizes]
        except ValueError:            # this topology can't run at n_cores
            continue
        ratio = 1.0
        if base_s:
            s = _format_roofline_seconds(backend, f"{fmt}+{sched}", dims)
            if s:
                ratio = s / base_s
        score = sum(model.const * ratio + model.alpha * plan.steps
                    + model.beta * plan.bytes_per_core
                    / max(plan.link_parallelism, 1.0)
                    for plan in plans) / len(plans)
        scored.append((spec, float(score)))
    scored.sort(key=lambda kv: (kv[1],
                                0 if kv[0].startswith("ell+pipelined")
                                else 1, kv[0]))
    return scored


def rank_partitions(model: CostModel, coo, n_cores: int, *,
                    topology: str = "hypercube", d: Optional[int] = None
                    ) -> List[Tuple[str, float, int]]:
    """Registered partitioners sorted by predicted step seconds on ``coo``.

    For each ``partition`` knob value this relabels the graph
    (``mincom`` → :func:`repro.graph.partition.mincom_assignment`; ``naive``
    → identity), measures the post-merge wire content with
    :func:`repro.graph.partition.exchange_rows`, plans the exchange with
    ``wire_rows`` so ``ExchangePlan.bytes_per_core`` reflects the measured
    cut, and scores it through ``model.predict`` — the partition axis seen
    by the SAME cost model that ranks topologies.  Returns
    ``[(name, predicted_seconds, bytes_per_core), ...]`` best-first; ties
    prefer ``naive`` (no relabeling work for no predicted win).
    """
    import numpy as np

    from repro.graph.partition import (PARTITIONS, exchange_rows,
                                       mincom_assignment,
                                       partition_permutation)

    from .registry import get_topology

    rows = np.asarray(coo.rows, np.int64)
    cols = np.asarray(coo.cols, np.int64)
    vals = np.asarray(coo.vals)
    d = int(d) if d is not None else model.d
    topo = get_topology(topology)
    scored = []
    for name in PARTITIONS:
        if name == "mincom" and n_cores > 1 and coo.n_dst == coo.n_src:
            assign = mincom_assignment(rows, cols, coo.n_dst, n_cores)
            perm = partition_permutation(assign, n_cores)
            r, c = perm[rows], perm[cols]
        else:
            r, c = rows, cols
        wr = exchange_rows(r, c, vals, coo.n_dst, coo.n_src, n_cores)
        plan = topo.plan(coo.n_dst, d, n_cores, cost_model=model,
                         wire_rows=wr)
        scored.append((name, float(plan.predicted_seconds),
                       int(plan.bytes_per_core)))
    scored.sort(key=lambda kv: (kv[1], 0 if kv[0] == "naive" else 1, kv[0]))
    return scored


# ---------------------------------------------------------------------------
# Resolution: the three tiers.
# ---------------------------------------------------------------------------
def _entry_key(backend: str, n_cores: int, bucket: str) -> str:
    return f"{backend}|P{n_cores}|{bucket}"


def _valid_concrete_spec(spec, n_cores: int) -> bool:
    from .config import EngineConfig
    from .registry import get_topology
    if not isinstance(spec, str):
        return False
    try:
        cfg = EngineConfig.from_spec(spec)
        if cfg.is_auto:
            return False
        get_topology(cfg.topology).validate_cores(n_cores)
        return True
    except ValueError:
        return False


def _persisted_spec(backend: str, n_cores: int,
                    graph_stats: Optional[GraphStats],
                    path: Optional[str]) -> Optional[str]:
    rec = PLANNER_STORE.load(path, warn_corrupt=True)
    if rec is None:
        return None
    entries = rec.get("entries")
    if not isinstance(entries, dict):
        warnings.warn(
            f"planner record {PLANNER_STORE.path(path)!r} has no 'entries' "
            "table; falling through", RuntimeWarning, stacklevel=3)
        return None
    prefix = _entry_key(backend, n_cores, "")
    keys = []
    if graph_stats is not None:
        keys.append(_entry_key(backend, n_cores, graph_stats.bucket()))
    # deterministic prefix fallback: any bucket measured at this
    # (backend, n_cores) beats the analytic tier, sorted-first on ties
    keys.extend(k for k in sorted(entries) if k.startswith(prefix)
                and k not in keys)
    for key in keys:
        ent = entries.get(key)
        spec = ent.get("spec") if isinstance(ent, dict) else None
        if _valid_concrete_spec(spec, n_cores):
            return spec
        if ent is not None:
            warnings.warn(
                f"planner entry {key!r} names a stale/unregistered spec "
                f"{spec!r}; falling through", RuntimeWarning, stacklevel=3)
    return None


def resolve_spec(*, n_cores: int,
                 graph_stats: Optional[GraphStats] = None,
                 backend: Optional[str] = None,
                 candidates: Optional[Sequence[str]] = None,
                 path: Optional[str] = None, mode: str = "train",
                 max_batch: int = 8) -> str:
    """The concrete spec ``"auto"`` stands for at ``n_cores``.

    Tier 1: a persisted :func:`autotune` winner for this
    (backend, n_cores, bucket) — measured beats modeled.  Tier 2: the
    analytic cost model fitted from the topology sweep record.  Tier 3:
    :data:`DEFAULT_SPEC`.  Pure reads — never measures, never sweeps —
    and always returns a registered spec.

    ``mode="serving"`` (the :class:`~repro.serving.InferenceEngine` path)
    skips tier 1 — autotune winners measure training step THROUGHPUT,
    the wrong objective for micro-batch latency — and ranks tier 2 with
    the latency-weighted serving objective over batch sizes
    ``1..max_batch`` (see :func:`rank_specs`).
    """
    backend = backend or _backend()
    if mode != "serving":
        spec = _persisted_spec(backend, n_cores, graph_stats, path)
        if spec is not None:
            return spec
    model = fit_cost_model(n_cores=n_cores, backend=backend)
    if model is not None:
        ranked = rank_specs(model, n_cores, graph_stats=graph_stats,
                            backend=backend, candidates=candidates,
                            mode=mode, max_batch=max_batch)
        if ranked:
            return ranked[0][0]
    return DEFAULT_SPEC


# ---------------------------------------------------------------------------
# Tier-1 producer: the compile-and-replay autotune harness.
# ---------------------------------------------------------------------------
def _round_up(v: int, mult: int) -> int:
    return max(((int(v) + mult - 1) // mult) * mult, mult)


def _autotune_measure(stats_kw: Optional[Dict], n_cores: int,
                      candidates: Sequence[str], n_steps: int,
                      n_trials: int, seed: int) -> Dict:
    """Measure every candidate bundle on one shared synthetic stream.

    Same methodology as ``benchmarks/epoch_time.py``: all arms run
    back-to-back inside every trial (host load is common-mode), the
    per-arm time is the median across trials, every arm's first-step loss
    must sit within 1e-5 of the first arm's (reduction-order roundoff
    only).  Needs ``n_cores`` devices — :func:`autotune` re-execs this in
    a child process with forced XLA_FLAGS when the parent has fewer.
    """
    import jax
    import numpy as np

    from repro.distributed.gcn_train import init_params
    from repro.graph.coo import from_edges
    from repro.launch.mesh import make_mesh

    from .engine import Engine

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    if stats_kw:
        mid = _round_up(stats_kw["n_dst"], n_cores)
        frontier = _round_up(stats_kw["n_src"], n_cores)
        deg = max(int(round(stats_kw["avg_deg"])), 1)
        feat = max(int(stats_kw["feat_dim"]), 8)
    else:
        mid, frontier, deg, feat = 256, 512, 8, 64
    batch = _round_up(mid // 2, n_cores)
    hidden = feat
    mesh = make_mesh((n_cores,), ("model",))
    rng = np.random.default_rng(seed)

    def layer(n_dst, n_src):
        e = n_dst * deg
        return from_edges(rng.integers(0, n_dst, e),
                          rng.integers(0, n_src, e),
                          np.abs(rng.standard_normal(e))
                          .astype(np.float32) + 0.1, n_dst, n_src)

    class _MB:                        # duck-typed MiniBatch: layers only
        pass

    _MB.layers = [layer(batch, mid), layer(mid, frontier)]
    x = rng.standard_normal((frontier, feat)).astype(np.float32)
    labels = rng.integers(0, 16, batch).astype(np.int32)
    runs, ref_loss, loss_match = {}, None, True
    for spec in candidates:
        bundle = Engine(spec).build(mesh)
        b = bundle.shard_batch(_MB(), x, labels)
        params = init_params(jax.random.PRNGKey(seed),
                             [(feat, hidden), (hidden, 16)])
        step = bundle.train_step_fn(b["dims"])
        params, loss = step(params, b)        # compile; loss at init params
        first = float(loss)
        params, loss = step(params, b)        # warmup
        jax.block_until_ready(loss)
        if ref_loss is None:
            ref_loss = first
        elif abs(first - ref_loss) > 1e-5:
            loss_match = False
        runs[spec] = {"step": step, "batch": b, "params": params,
                      "times": []}
    for _ in range(n_trials):
        for arm in runs.values():     # back-to-back: load is common-mode
            t0 = time.perf_counter()
            p, loss = arm["params"], None
            for _ in range(n_steps):
                p, loss = arm["step"](p, arm["batch"])
            jax.block_until_ready(loss)
            arm["times"].append((time.perf_counter() - t0) / n_steps)
    s = {spec: sorted(arm["times"])[len(arm["times"]) // 2]
         for spec, arm in runs.items()}
    winner = min(sorted(s), key=lambda k: s[k])
    return {"winner": winner, "s_per_step": s, "loss_match": loss_match,
            "stream": {"batch": batch, "mid": mid, "frontier": frontier,
                       "feat": feat, "deg": deg}}


def _autotune_measure_child(stats_kw: Optional[Dict], n_cores: int,
                            candidates: Sequence[str], n_steps: int,
                            n_trials: int, seed: int) -> Dict:
    """Re-exec :func:`_autotune_measure` under a forced multi-device
    backend (XLA_FLAGS must precede the jax import)."""
    child = (
        "import json;"
        "from repro.engine.planner import _autotune_measure;"
        f"print(json.dumps(_autotune_measure({stats_kw!r}, {n_cores!r}, "
        f"{list(candidates)!r}, {n_steps!r}, {n_trials!r}, {seed!r})))"
    )
    from repro.launch.mesh import forced_host_env

    src = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = forced_host_env(n_cores, src)
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"planner autotune child failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def autotune(graph_stats: Optional[GraphStats] = None, *,
             n_cores: int = 4,
             candidates: Optional[Sequence[str]] = None,
             n_steps: int = 3, n_trials: int = 8, seed: int = 0,
             path: Optional[str] = None, force: bool = False) -> Dict:
    """Time every candidate spec bundle, persist the winner, return the
    entry.

    Idempotent per (backend, n_cores, bucket) key unless ``force`` — a
    machine autotunes once per workload bucket; training never re-tunes.
    Entries merge into the existing ``BENCH_planner.json`` so different
    core counts and buckets accumulate in one file.
    """
    import jax

    backend = _backend()
    candidates = list(candidates) if candidates is not None \
        else supported_specs(three_part=True)
    bucket = graph_stats.bucket() if graph_stats is not None else "default"
    key = _entry_key(backend, n_cores, bucket)
    rec = PLANNER_STORE.load(path) or {}
    entries = rec.get("entries")
    if not isinstance(entries, dict):
        entries = {}
    if not force:
        ent = entries.get(key)
        if isinstance(ent, dict) and _valid_concrete_spec(ent.get("spec"),
                                                          n_cores):
            return ent
    stats_kw = dataclasses.asdict(graph_stats) \
        if graph_stats is not None else None
    if len(jax.devices()) >= n_cores:
        meas = _autotune_measure(stats_kw, n_cores, candidates, n_steps,
                                 n_trials, seed)
    else:
        meas = _autotune_measure_child(stats_kw, n_cores, candidates,
                                       n_steps, n_trials, seed)
    entry = {
        "spec": meas["winner"], "backend": backend, "n_cores": n_cores,
        "bucket": bucket, "graph_stats": stats_kw,
        "s_per_step": meas["s_per_step"], "loss_match": meas["loss_match"],
        "stream": meas.get("stream"), "candidates": list(candidates),
        "n_steps": n_steps, "n_trials": n_trials, "seed": seed,
    }
    entries[key] = entry
    PLANNER_STORE.save({"entries": entries}, path)
    return entry
