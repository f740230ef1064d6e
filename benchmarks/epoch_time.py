"""Table 2 reproduction — per-epoch training time, ours vs the naive
(HP-GNN-style) dataflow.

The FPGA cannot be timed here, so the reproduction has two layers:

  1. **Analytic model at the paper's scale**: per-epoch op counts from the
     Table-1 cost model at the paper's setup (batch 1024, NS (25, 10),
     hidden 256), for the naive dataflow vs ours.  The paper's headline is
     1.03×–1.81× over HP-GNN; our model isolates the DATAFLOW component of
     that gap (the NoC/NUMA component shows up in the ctc benchmark).
  2. **Measured at reduced scale**: wall-clock s/epoch of the actual jitted
     training step on the synthetic datasets, ours vs naive, same seeds.

``--overlap`` runs the measured engine-arm comparison (paper §4.3, Fig. 9):
the distributed train step on a forced multi-device CPU backend, the serial
``coo+serial`` oracle vs every arm in ``--arms`` (engine spec strings,
default ``block+pipelined,ell+pipelined`` — the old ``--ell/--no-ell`` flag
pair collapsed into specs), same graph and seeds — reporting the measured
step-time speedup per arm.  Because XLA_FLAGS must be set before jax
imports, the arm measurement re-executes itself in a child process; results
land in ``BENCH_overlap.json``.

``--input-pipeline {sync,prefetch,both}`` measures the engine-native
Trainer's per-step host-stall time under a synchronous vs a prefetching
(background-thread, depth-2) input pipeline — the overlap win of taking
sampling + per-batch layout build off the step critical path; results land
in ``BENCH_input_pipeline.json`` and, via ``run --smoke``, in
``BENCH_smoke.json`` under ``input_pipeline``.

``--feature-store`` measures feature residency: the dense device-resident
baseline vs the ``host`` and ``mmap`` :mod:`repro.featurestore` backends,
each under a synchronous and a STAGED prefetching pipeline (sample →
gather → layout → place, one thread per stage) on one bit-matching
stream, with a hot-vertex cache in front of the store; results land in
``BENCH_feature_store.json`` and ``run --smoke`` gates
``prefetch_reduces_stall`` + ``loss_match`` + ``cache_hit_rate > 0``.

``--topologies`` sweeps every registered interconnect topology (hypercube,
allpairs, ring, torus2d, plus anything registered since) over ONE
bit-matching synthetic stream: same graph, same batch, same seeds, only
the exchange wires differ.  Per topology it records the analytic exchange
plan (steps, bytes/core — ``Topology.plan``), the measured train-step
time, and the paired-median aggregate-op speedup vs the dense ``allpairs``
reference; results land in ``BENCH_topology.json``.  ``run --smoke`` gates
``hypercube_vs_allpairs_speedup > 1`` at 4 cores — the structured NoC must
beat the dense crossbar reference, or the headline topology claim is dead.

``--redundancy`` races the GraphACT-merged engine (``merge="redundancy"``
+ ``partition="mincom"``) against the plain ELL arm on one bit-matching
synthetic power-law community stream — same layers, features, labels,
initial params; results (wire-bytes reduction, aggregation FLOP reduction,
paired-median step speedup) land in ``BENCH_redundancy.json`` and ``run
--smoke`` gates ``loss_match`` + ``wire_bytes_reduction > 1.0`` +
``flop_reduction > 1.0``.

``--auto`` exercises the profile-guided planner end to end: autotune every
candidate spec on one synthetic stream (compile-and-replay, same
paired-median child-re-exec methodology), persist the winner to
``BENCH_planner.json``, then race a fresh ``Engine("auto")`` — which must
resolve through that record — against the best manual arm.  Results land
in ``BENCH_auto.json``; ``run --smoke`` gates
``auto_vs_best_manual_speedup >= 0.9`` plus exact loss bit-match and
winner/resolution agreement.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimator import LayerShape, time_naive, time_ours
from repro.graph import NeighborSampler, make_dataset
from repro.launch.mesh import forced_host_env, make_mesh
from repro.graph.datasets import DATASET_STATS
from repro.models.gcn_model import GCNConfig, gcn_loss, init_gcn_params
from repro.optim import apply_updates, sgd

from .dataflow_table1 import BATCH, FANOUTS, HIDDEN, paper_layer_shapes


def _time_naive_realistic(s: LayerShape, order: str) -> float:
    """Implementation-realistic baseline transpose costs: the Aᵀ table is an
    O(e log e) COO re-sort (not Table 1's literal O(n̄e) bound) and the
    feature transpose an O(n̄d) copy — what a software HP-GNN-style port
    would actually pay.  Keeps the Table-2 comparison honest."""
    import math
    base = time_ours(s, order) - (s.h * s.d + s.b * s.c)
    resort = s.e * max(math.log2(max(s.e, 2)), 1.0)
    feat_t = (s.nbar if order == "coag" else s.n) * s.d
    return float(base + resort + feat_t + s.h * s.d)


def analytic_epoch_ratio() -> List[Dict]:
    rows = []
    for name, st in DATASET_STATS.items():
        shapes = paper_layer_shapes(name)
        batches = st.n_nodes // BATCH
        naive_lit = sum(min(time_naive(s, "coag"), time_naive(s, "agco"))
                        for s in shapes) * batches
        naive_real = sum(min(_time_naive_realistic(s, "coag"),
                             _time_naive_realistic(s, "agco"))
                         for s in shapes) * batches
        ours = sum(min(time_ours(s, "coag"), time_ours(s, "agco"))
                   for s in shapes) * batches
        rows.append({"dataset": name, "ops_naive": naive_lit,
                     "ops_naive_realistic": naive_real, "ops_ours": ours,
                     "speedup_paper_literal": naive_lit / ours,
                     "speedup": naive_real / ours})
    return rows


def measured_epoch(name: str, scale: float = 0.01, batch: int = 64,
                   n_batches: int = 8, seed: int = 0) -> Dict:
    ds = make_dataset(name, scale=scale, feat_dim=64)
    sampler = NeighborSampler(ds.graph, fanouts=FANOUTS, pad_multiple=16,
                              seed=seed)
    out = {}
    rng = np.random.default_rng(seed)
    seeds_list = [rng.permutation(ds.graph.n_nodes)[:batch]
                  for _ in range(n_batches)]
    nnz_pad = sampler.static_nnz(batch)
    batches = []
    for sd in seeds_list:
        mb = sampler.sample(sd, nnz_pad=nnz_pad,
                            rng=np.random.default_rng(0))
        x = jnp.asarray(ds.features[np.minimum(mb.input_nodes,
                                               ds.graph.n_nodes - 1)])
        pad = mb.layers[0].n_dst - len(sd)
        lab = ds.labels[np.pad(sd, (0, pad))]
        if lab.ndim > 1:
            lab = lab.argmax(-1).astype(np.int32)
        batches.append((mb.layers, x, jnp.asarray(lab)))
    for dataflow in ("ours", "naive"):
        cfg = GCNConfig(name=name, feat_dim=64, hidden=HIDDEN,
                        n_classes=ds.stats.n_classes, dataflow=dataflow)
        params = init_gcn_params(jax.random.PRNGKey(seed), cfg)
        init, update = sgd(0.05)
        opt = init(params)
        orders = ("agco", "agco")

        @jax.jit
        def step(params, opt, layers, x, lab):
            loss, g = jax.value_and_grad(gcn_loss)(params, layers, x, lab,
                                                   cfg, orders,
                                                   n_valid=batch)
            upd, opt = update(g, opt, params)
            return apply_updates(params, upd), opt, loss

        # warmup compile
        params, opt, _ = step(params, opt, *batches[0])
        t0 = time.perf_counter()
        for layers, x, lab in batches:
            params, opt, loss = step(params, opt, layers, x, lab)
        jax.block_until_ready(loss)
        out[dataflow] = (time.perf_counter() - t0) / n_batches
    out["speedup"] = out["naive"] / out["ours"]
    return out


# ---------------------------------------------------------------------------
# --overlap arms: the serial oracle vs each engine spec, measured.
# ---------------------------------------------------------------------------
#: legacy metric names per spec — keeps BENCH_overlap.json keys (and the
#: compare.py tracked paths) stable across the Engine migration; an
#: unlisted spec records under its spec string
ARM_NAMES = {"coo+serial": "serial", "block+pipelined": "overlap",
             "ell+pipelined": "ell"}
DEFAULT_ARMS = ("block+pipelined", "ell+pipelined")


def _arm_name(spec: str) -> str:
    return ARM_NAMES.get(spec, spec.replace("+", "_"))


def _synthetic_layers(batch: int, mid: int, frontier: int, deg: int,
                      seed: int = 0):
    """Two sampled layers of a synthetic power-graph (COO, deepest last).

    Generated ONCE per benchmark run and shared by every arm, so all arms
    aggregate the same graph — and the ELL arm's cached EdgePlan (keyed on
    the COO identity) is demonstrably built once and reused across all
    measured steps.
    """
    from repro.graph.coo import from_edges

    rng = np.random.default_rng(seed)

    def layer(n_dst, n_src):
        e = n_dst * deg
        return from_edges(rng.integers(0, n_dst, e),
                          rng.integers(0, n_src, e),
                          np.abs(rng.standard_normal(e)).astype(np.float32)
                          + 0.1,
                          n_dst, n_src)

    return [layer(batch, mid), layer(mid, frontier)]


def _synthetic_sharded_batch(bundle, batch: int, frontier: int, feat: int,
                             layers, seed: int = 0) -> Dict:
    """Shared synthetic layers → device-ready sharded batch through one
    engine bundle (the bundle's mesh commits every leaf to its core-axis
    sharding at build time — placement once per minibatch, not per step)."""
    rng = np.random.default_rng(seed + 1)

    class _MB:                       # duck-typed MiniBatch: layers only
        pass

    _MB.layers = layers
    x = rng.standard_normal((frontier, feat)).astype(np.float32)
    labels = rng.integers(0, 16, batch).astype(np.int32)
    return bundle.shard_batch(_MB(), x, labels)


def measured_overlap(n_cores: int = 8, batch: int = 512, mid: int = 2048,
                     frontier: int = 8192, feat: int = 256,
                     hidden: int = 256, deg: int = 16, n_steps: int = 3,
                     n_trials: int = 12, n_chunks=None, seed: int = 0,
                     arms=DEFAULT_ARMS) -> Dict:
    """Step time of the distributed GCN train step: the ``coo+serial``
    oracle vs every engine spec in ``arms``.  Must run under a multi-device
    backend.

    All arms run back-to-back inside every trial and each reported speedup
    is the MEDIAN of the per-trial serial/arm ratios: on shared/
    oversubscribed hosts (P device threads on few physical cores) absolute
    step times swing 2-3× with background load, but the load is common-mode
    across an adjacent group, so the paired ratio is stable where a
    ratio-of-minimums is not.  Minimum per-step times are reported
    alongside for reference.  Every arm's batch is committed to its device
    sharding at build time (the fix for the recorded
    ``agg_fwd_speedup < 1`` regression — uncommitted edge arrays were
    re-laid-out on every step, a cost that grew with the blocked layout's
    leaf sizes); the ELL arm's EdgePlan is built once, cache-verified, and
    reused across all measured steps.
    """
    from repro.distributed.aggregate import shard_edges_ell
    from repro.distributed.gcn_train import init_params
    from repro.engine import Engine, EngineConfig

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    mesh = make_mesh((n_cores,), ("model",))
    # canonicalize first (' ell' / bare 'ell' → 'ell+pipelined') so the
    # legacy-key mapping, dedupe, and the oracle filter all see one
    # spelling; the oracle always runs — listing it as an arm would only
    # race it against itself (and collide on the 'serial' metric names)
    arms = tuple(EngineConfig.from_spec(s).spec for s in arms)
    arms = tuple(dict.fromkeys(s for s in arms if s != "coo+serial"))
    out: Dict = {"n_cores": n_cores, "batch": batch, "mid": mid,
                 "frontier": frontier, "feat": feat, "hidden": hidden,
                 "deg": deg, "n_steps": n_steps, "n_trials": n_trials,
                 "n_chunks": n_chunks, "arms": list(arms)}
    variants = [("serial", "coo+serial")] + [(_arm_name(s), s) for s in arms]
    ell = "ell+pipelined" in arms
    layers = _synthetic_layers(batch, mid, frontier, deg, seed)
    from repro.kernels import edgeplan
    misses_at_start = edgeplan.cache_stats()["misses"]
    runs = {}
    for arm, spec in variants:
        # the power-of-two core-count check lives in Engine.build now
        bundle = Engine(EngineConfig.from_spec(
            spec, lr=0.05, n_chunks=n_chunks)).build(mesh)
        b = _synthetic_sharded_batch(bundle, batch, frontier, feat,
                                     layers=layers, seed=seed)
        params = init_params(jax.random.PRNGKey(seed),
                             [(feat, hidden), (hidden, 16)])
        step = bundle.train_step_fn(b["dims"])
        params, loss = step(params, b)        # compile
        params, loss = step(params, b)        # warmup
        jax.block_until_ready(loss)
        runs[arm] = {"step": step, "batch": b, "params": params,
                     "loss": float(loss), "times": []}
    # plan builds for THESE layers: misses added while the arms were set up
    # (shard_edges_ell and the engine layout caches share the edgeplan
    # cache; the layer-shard builds dominate the count)
    builds_setup = edgeplan.cache_stats()["misses"] - misses_at_start
    for _ in range(n_trials):
        for arm in runs.values():
            t0 = time.perf_counter()
            params, loss = arm["params"], None
            for _ in range(n_steps):
                params, loss = arm["step"](params, arm["batch"])
            jax.block_until_ready(loss)
            arm["times"].append((time.perf_counter() - t0) / n_steps)
    out["s_per_step_serial"] = min(runs["serial"]["times"])
    out["loss_serial"] = runs["serial"]["loss"]
    for arm in runs:
        if arm == "serial":
            continue
        suffix = "" if arm == "overlap" else f"_{arm}"
        ratios = sorted(s / o for s, o in zip(runs["serial"]["times"],
                                              runs[arm]["times"]))
        out[f"s_per_step_{arm}"] = min(runs[arm]["times"])
        out[f"trial_ratios{suffix}"] = [round(r, 3) for r in ratios]
        out[f"loss_{arm}"] = runs[arm]["loss"]
        out[f"loss_match{suffix}"] = abs(out["loss_serial"]
                                         - runs[arm]["loss"]) < 1e-5
        out[f"speedup{suffix}"] = ratios[len(ratios) // 2]  # paired median
    out.update(_measured_overlap_aggregate_op(
        n_cores, mid, frontier, hidden, deg, n_trials * n_steps, seed,
        arms=arms, n_chunks=n_chunks))
    if ell:
        # EdgePlan cache proof: the plans the measured steps consumed are
        # STILL the cached objects — re-requesting every layer's shards
        # after all timed work must add zero builder misses (a per-step or
        # per-arm rebuild would have shown up as misses during the runs;
        # the shard build inside shard_batch was the one and only).
        misses_before = edgeplan.cache_stats()["misses"]
        for coo in layers:
            shard_edges_ell(coo, n_cores)
        out["edge_plan_cached"] = (edgeplan.cache_stats()["misses"]
                                   == misses_before)
        out["edge_plan_builds"] = builds_setup     # one per layer expected
    return out


def _measured_overlap_aggregate_op(n_cores: int, n_dst: int, n_src: int,
                                   d: int, deg: int, n_pairs: int,
                                   seed: int, arms=DEFAULT_ARMS,
                                   n_chunks=None) -> Dict:
    """The hot path in isolation: the serial oracle vs every engine arm's
    aggregate, forward and forward+backward, paired per call (the arms of a
    pair run back to back so host-load noise is common-mode).

    Inside the full train step the aggregation savings can hide under
    unrelated gradient work on an oversubscribed CPU host, so the op-level
    ratios are reported alongside the step-level ones.  ``bundle.aggregator``
    commits every edge leaf to its core-axis sharding up front — the SAME
    placement rule the training pipeline runs once per minibatch — so the
    ratios measure the schedule, not jit's per-call re-layout of
    uncommitted operands.
    """
    from repro.distributed.sharding import leading_axis_put
    from repro.engine import Engine, EngineConfig
    from repro.graph.coo import from_edges

    rng = np.random.default_rng(seed)
    e = n_dst * deg
    coo = from_edges(rng.integers(0, n_dst, e), rng.integers(0, n_src, e),
                     np.abs(rng.standard_normal(e)).astype(np.float32) + 0.1,
                     n_dst, n_src)
    mesh = make_mesh((n_cores,), ("model",))
    x = leading_axis_put(mesh,
                         rng.standard_normal((n_src, d)).astype(np.float32))
    ser = Engine("coo+serial").build(mesh, graph=coo).aggregator()
    gs = jax.jit(jax.grad(lambda xx: jnp.sum(ser(xx) ** 2)))

    def paired(f1, args1, f2, args2):
        jax.block_until_ready(f1(*args1))
        jax.block_until_ready(f2(*args2))
        rs = []
        for _ in range(n_pairs):
            t0 = time.perf_counter()
            jax.block_until_ready(f1(*args1))
            t1 = time.perf_counter()
            jax.block_until_ready(f2(*args2))
            rs.append((t1 - t0) / (time.perf_counter() - t1))
        rs.sort()
        return rs[len(rs) // 2]

    out: Dict = {}
    for spec in arms:
        name = _arm_name(spec)
        suffix = "" if name == "overlap" else f"_{name}"
        fn = Engine(EngineConfig.from_spec(spec, n_chunks=n_chunks)) \
            .build(mesh, graph=coo).aggregator()
        gf = jax.jit(jax.grad(lambda xx, fn=fn: jnp.sum(fn(xx) ** 2)))
        out[f"agg_fwd_speedup{suffix}"] = paired(ser, (x,), fn, (x,))
        out[f"agg_fwdbwd_speedup{suffix}"] = paired(gs, (x,), gf, (x,))
    return out


def run_overlap_arm(n_cores: int = 8, *, smoke: bool = False,
                    arms=DEFAULT_ARMS,
                    out_path: str = "BENCH_overlap.json") -> Dict:
    """Re-exec the engine-arm measurement under a forced multi-device
    backend (XLA_FLAGS must precede the jax import) and write ``out_path``.

    ``arms`` are engine spec strings, validated against the registry before
    the child process launches.
    """
    from repro.engine import EngineConfig

    # canonicalize + fail fast (listing registered options), dedupe, and
    # drop the oracle — it always runs as the baseline of every pair
    arms = tuple(EngineConfig.from_spec(s).spec for s in arms)
    arms = tuple(dict.fromkeys(s for s in arms if s != "coo+serial"))
    kwargs = {"n_cores": n_cores, "arms": arms}
    if smoke:
        kwargs.update(batch=128, mid=256, frontier=512, feat=64, hidden=64,
                      deg=8, n_steps=3)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_overlap;"
        f"print(json.dumps(measured_overlap(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"overlap arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## measured engine arms ({n_cores} simulated cores): "
          f"coo+serial vs {', '.join(arms)}")
    print("arm,s_per_step")
    print(f"serial,{rec['s_per_step_serial']:.4f}")
    for spec in arms:
        name = _arm_name(spec)
        print(f"{name},{rec[f's_per_step_{name}']:.4f}")
    for spec in arms:
        name = _arm_name(spec)
        suffix = "" if name == "overlap" else f"_{name}"
        print(f"# {spec}: train-step {rec[f'speedup{suffix}']:.3f}x  "
              f"agg fwd {rec[f'agg_fwd_speedup{suffix}']:.3f}x  "
              f"fwd+bwd {rec[f'agg_fwdbwd_speedup{suffix}']:.3f}x  "
              f"loss_match={rec[f'loss_match{suffix}']}"
              + (f"  plan_cached={rec.get('edge_plan_cached')}"
                 if name == "ell" else "")
              + "  (paired median)")
    print(f"# (wrote {out_path})")
    return rec


# ---------------------------------------------------------------------------
# --redundancy: GraphACT-merged ELL + mincom partitioning vs plain ELL.
# ---------------------------------------------------------------------------
def _synthetic_powerlaw_layers(batch: int, mid: int, frontier: int,
                               deg: int, n_cores: int, seed: int = 0):
    """Two sampled layers of a power-law community graph (COO, deepest
    last) — the bench graph BOTH redundancy tiers need to show a win.

    Two properties are load-bearing:

      * **Zipf hubs inside planted communities**: each destination draws
        ~90% of its neighbors from its own community's source pool under a
        zipf(1.2) rank weighting, so many rows share the same top hub
        PAIRS — the structural sharing :func:`mine_pair_redundancy`
        factors into virtual vertices.  Edge weights are GCN symmetric
        normalization (``1/sqrt(d_dst * d_src)``) — the normalization
        makes every shared pair's coefficients proportional across rows
        (ratio ``sqrt(d_v/d_u)``), which is what lets structural sharing
        actually merge; independent random weights would yield zero.
      * **Shuffled node labels in the deeper spaces**: community
        membership is a random permutation of ids for the mid/frontier
        spaces (space 0 keeps naive blocks — the batch placement mincom
        must respect), so the naive contiguous split cuts ~uniform
        cross-core traffic while ``mincom`` can recover the planted
        communities and cut it.  10% of edges rewire uniformly — the
        irreducible cross traffic.
    """
    from repro.graph.coo import from_edges

    rng = np.random.default_rng(seed)
    comm = [np.minimum(np.arange(batch) // max(batch // n_cores, 1),
                       n_cores - 1),
            rng.permutation(np.arange(mid) % n_cores),
            rng.permutation(np.arange(frontier) % n_cores)]

    def layer(n_dst, n_src, cd, cs):
        rows_l, cols_l = [], []
        for c in range(n_cores):
            dsts = np.where(cd == c)[0]
            pool = rng.permutation(np.where(cs == c)[0])
            w = 1.0 / np.arange(1.0, pool.size + 1.0) ** 1.2
            w /= w.sum()
            e_c = dsts.size * deg
            cols_c = pool[rng.choice(pool.size, e_c, p=w)]
            cross = rng.random(e_c) < 0.1
            cols_c[cross] = rng.integers(0, n_src, int(cross.sum()))
            rows_l.append(np.repeat(dsts, deg))
            cols_l.append(cols_c)
        rows = np.concatenate(rows_l).astype(np.int64)
        cols = np.concatenate(cols_l).astype(np.int64)
        # collapse duplicate (r,c) draws, then weight by GCN symmetric
        # normalization over the deduped structure
        keep = np.unique(rows * n_src + cols)
        rows, cols = keep // n_src, keep % n_src
        d_dst = np.bincount(rows, minlength=n_dst).astype(np.float64)
        d_src = np.bincount(cols, minlength=n_src).astype(np.float64)
        vals = (1.0 / np.sqrt(np.maximum(d_dst[rows] * d_src[cols], 1.0))
                ).astype(np.float32)
        return from_edges(rows, cols, vals, n_dst, n_src)

    return [layer(batch, mid, comm[0], comm[1]),
            layer(mid, frontier, comm[1], comm[2])]


def measured_redundancy(n_cores: int = 4, batch: int = 256, mid: int = 1024,
                        frontier: int = 2048, feat: int = 128,
                        hidden: int = 128, deg: int = 12, n_steps: int = 3,
                        n_trials: int = 12, seed: int = 0) -> Dict:
    """The merged arm (``merge="redundancy"`` + ``partition="mincom"``) vs
    the plain ELL engine on one bit-matching power-law stream.

    Both arms consume the SAME layers, features, labels and initial params;
    the merged arm's mincom relabeling keeps space 0 (batch/labels/logits)
    identity, so the first-step losses must agree to ≤1e-5 — reduction-
    order roundoff only.  Reported per arm: the measured exchange
    ``wire_bytes`` from the engine's plan report (post-merge row accounting
    through ``Topology.plan``), the aggregation FLOP reduction from the
    GraphACT merge stats, and the paired-median step-time ratio (arms run
    back-to-back per trial — host-load noise is common-mode, as in
    :func:`measured_overlap`).
    """
    from repro.distributed.gcn_train import init_params
    from repro.engine import Engine, EngineConfig

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    mesh = make_mesh((n_cores,), ("model",))
    layers = _synthetic_powerlaw_layers(batch, mid, frontier, deg, n_cores,
                                        seed)
    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal((frontier, feat)).astype(np.float32)
    labels = rng.integers(0, 16, batch).astype(np.int32)

    class _MB:                        # duck-typed MiniBatch: layers only
        pass

    _MB.layers = layers
    arms = [("base", EngineConfig.from_spec("ell+pipelined+hypercube")),
            ("merged", EngineConfig.from_spec(
                "ell+pipelined+hypercube+mincom", merge="redundancy"))]
    out: Dict = {"n_cores": n_cores, "batch": batch, "mid": mid,
                 "frontier": frontier, "feat": feat, "hidden": hidden,
                 "deg": deg, "n_steps": n_steps, "n_trials": n_trials,
                 "base_spec": arms[0][1].spec, "merged_spec": arms[1][1].spec}
    runs = {}
    for name, cfg in arms:
        bundle = Engine(cfg).build(mesh)
        b = bundle.shard_batch(_MB(), x, labels)
        params = init_params(jax.random.PRNGKey(seed),
                             [(feat, hidden), (hidden, 16)])
        step = bundle.train_step_fn(b["dims"])
        _, loss = step(params, b)     # compile; first-step loss for the
        jax.block_until_ready(loss)   # bit-match gate (same params0)
        runs[name] = {"step": step, "batch": b, "params": params,
                      "loss": float(loss),
                      "report": bundle.plan_report(_MB(), feat),
                      "times": []}
    for _ in range(n_trials):
        for arm in runs.values():
            t0 = time.perf_counter()
            params, loss = arm["params"], None
            for _ in range(n_steps):
                params, loss = arm["step"](params, arm["batch"])
            jax.block_until_ready(loss)
            arm["times"].append((time.perf_counter() - t0) / n_steps)
    for name, arm in runs.items():
        out[f"loss_{name}"] = arm["loss"]
        out[f"s_per_step_{name}"] = min(arm["times"])
        out[f"wire_bytes_{name}"] = arm["report"]["wire_bytes"]
    out["loss_match"] = abs(out["loss_base"] - out["loss_merged"]) < 1e-5
    out["wire_bytes_reduction"] = (out["wire_bytes_base"]
                                   / max(out["wire_bytes_merged"], 1.0))
    mrep = runs["merged"]["report"]
    out["flop_reduction"] = mrep["flop_reduction"]
    out["virtual_vertices"] = mrep["virtual_vertices"]
    out["pair_coverage"] = mrep["pair_coverage"]
    ratios = sorted(b / m for b, m in zip(runs["base"]["times"],
                                          runs["merged"]["times"]))
    out["trial_ratios"] = [round(r, 3) for r in ratios]
    out["step_speedup"] = ratios[len(ratios) // 2]     # paired median
    return out


def run_redundancy_arm(n_cores: int = 4, *, smoke: bool = False,
                       out_path: str = "BENCH_redundancy.json") -> Dict:
    """Re-exec :func:`measured_redundancy` under a forced multi-device
    backend (XLA_FLAGS must precede the jax import) and write ``out_path``.
    """
    kwargs = {"n_cores": n_cores}
    if smoke:
        kwargs.update(batch=128, mid=256, frontier=512, feat=64, hidden=64,
                      deg=8, n_steps=3, n_trials=8)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_redundancy;"
        f"print(json.dumps(measured_redundancy(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"redundancy arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## redundancy arm ({n_cores} simulated cores): "
          f"{rec['base_spec']} vs {rec['merged_spec']} (merge=redundancy)")
    print(f"# wire bytes/core: {rec['wire_bytes_base']:.3g} -> "
          f"{rec['wire_bytes_merged']:.3g}  "
          f"({rec['wire_bytes_reduction']:.2f}x reduction)")
    print(f"# aggregation FLOPs: {rec['flop_reduction']:.3f}x reduction  "
          f"({rec['virtual_vertices']:.0f} virtual vertices, "
          f"pair coverage {rec['pair_coverage']:.2f})")
    print(f"# step time: {rec['s_per_step_base']:.4f}s -> "
          f"{rec['s_per_step_merged']:.4f}s  "
          f"(paired-median speedup {rec['step_speedup']:.3f}x)  "
          f"loss_match={rec['loss_match']}")
    print(f"# (wrote {out_path})")
    return rec


# ---------------------------------------------------------------------------
# --topologies: every registered interconnect on one bit-matching stream.
# ---------------------------------------------------------------------------
def measured_topologies(n_cores: int = 4, base_spec: str = "ell+pipelined",
                        batch: int = 256, mid: int = 512,
                        frontier: int = 1024, feat: int = 128,
                        hidden: int = 128, deg: int = 8, n_steps: int = 3,
                        n_trials: int = 12, seed: int = 0) -> Dict:
    """Train-step + aggregate-op time per registered topology, one stream.

    Every topology consumes the SAME synthetic layers, features, labels and
    params (the bit-matching stream): only the exchange wires differ, so
    loss gaps measure reduction-order roundoff (must stay ≤1e-5) and time
    gaps measure the interconnect.  The dense ``allpairs`` crossbar is the
    baseline of every paired ratio — the structured topologies exist to
    beat it.  Alongside the measurements, each topology's analytic exchange
    plan (steps, bytes/core, max single-step message) is recorded from
    ``Topology.plan`` so the cost table never drifts from the code.
    """
    from repro.distributed.gcn_train import init_params
    from repro.engine import Engine, EngineConfig, supported_specs
    from repro.engine.registry import get_topology

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    base = EngineConfig.from_spec(base_spec)
    # the canonical three-part enumeration (respects the base format's
    # topology restrictions) — not a hand-built registry product
    prefix = f"{base.format}+{base.schedule}+"
    topologies = sorted(s[len(prefix):]
                        for s in supported_specs(three_part=True)
                        if s.startswith(prefix))
    mesh = make_mesh((n_cores,), ("model",))
    layers = _synthetic_layers(batch, mid, frontier, deg, seed)
    out: Dict = {"n_cores": n_cores, "backend": jax.default_backend(),
                 "base_spec": f"{base.format}+"
                 f"{base.schedule}", "batch": batch, "mid": mid,
                 "frontier": frontier, "feat": feat, "hidden": hidden,
                 "deg": deg, "n_steps": n_steps, "n_trials": n_trials,
                 "topologies": topologies}
    runs = {}
    for topo in topologies:
        plan = get_topology(topo).plan(mid, feat, n_cores)
        out[f"exchange_steps_{topo}"] = plan.steps
        out[f"exchange_bytes_per_core_{topo}"] = plan.bytes_per_core
        out[f"max_step_rows_{topo}"] = plan.max_step_rows
        out[f"link_parallelism_{topo}"] = plan.link_parallelism
        bundle = Engine(EngineConfig(format=base.format,
                                     schedule=base.schedule,
                                     topology=topo, lr=0.05)).build(mesh)
        b = _synthetic_sharded_batch(bundle, batch, frontier, feat,
                                     layers=layers, seed=seed)
        params = init_params(jax.random.PRNGKey(seed),
                             [(feat, hidden), (hidden, 16)])
        step = bundle.train_step_fn(b["dims"])
        params, loss = step(params, b)        # compile
        # loss_match compares THIS loss: every arm evaluates it at the
        # identical initial params on the identical batch, so the gap is
        # forward-only reduction-order roundoff — an optimizer-amplified
        # later-step loss would make the 1e-5 gate flap on unlucky seeds
        first_loss = float(loss)
        params, loss = step(params, b)        # warmup
        jax.block_until_ready(loss)
        runs[topo] = {"step": step, "batch": b, "params": params,
                      "loss": first_loss, "times": []}
    for _ in range(n_trials):
        for arm in runs.values():       # back-to-back: load is common-mode
            t0 = time.perf_counter()
            params, loss = arm["params"], None
            for _ in range(n_steps):
                params, loss = arm["step"](params, arm["batch"])
            jax.block_until_ready(loss)
            arm["times"].append((time.perf_counter() - t0) / n_steps)
    ref_loss = runs["hypercube"]["loss"]
    out["loss_match"] = True
    for topo, arm in runs.items():
        out[f"s_per_step_{topo}"] = min(arm["times"])
        out[f"loss_{topo}"] = arm["loss"]
        if abs(arm["loss"] - ref_loss) > 1e-5:
            out["loss_match"] = False
        if topo != "allpairs":
            ratios = sorted(a / t for a, t in
                            zip(runs["allpairs"]["times"], arm["times"]))
            out[f"step_speedup_vs_allpairs_{topo}"] = \
                ratios[len(ratios) // 2]                  # paired median
    out.update(_measured_topology_aggregate_op(
        n_cores, mid, frontier, feat, deg, n_trials * n_steps, seed,
        base=base, topologies=topologies))
    # the headline ratio the smoke gates and compare.py tracks: the paper's
    # NoC vs the dense crossbar reference, on the aggregation hot path
    out["hypercube_vs_allpairs_speedup"] = \
        out["agg_fwdbwd_speedup_vs_allpairs_hypercube"]
    # fit the planner's α·steps + β·bytes cost model against THESE
    # measurements and record each topology's prediction next to its
    # measured time, so the fit error is visible in the record itself
    from repro.engine import planner
    model = planner.fit_cost_model(record=out)
    if model is not None:
        out["cost_model"] = {"alpha": model.alpha, "beta": model.beta,
                             "const": model.const}
        for topo in topologies:
            plan = get_topology(topo).plan(mid, feat, n_cores,
                                           cost_model=model)
            out[f"predicted_s_per_step_{topo}"] = plan.predicted_seconds
            meas = out[f"s_per_step_{topo}"]
            out[f"predicted_rel_err_{topo}"] = \
                abs(plan.predicted_seconds - meas) / max(meas, 1e-12)
    return out


def _measured_topology_aggregate_op(n_cores: int, n_dst: int, n_src: int,
                                    d: int, deg: int, n_pairs: int,
                                    seed: int, base, topologies) -> Dict:
    """The exchange in isolation: aggregate fwd and fwd+bwd per topology,
    paired against the allpairs reference call-by-call (same methodology
    as :func:`_measured_overlap_aggregate_op` — common-mode host load
    cancels in the per-pair ratio)."""
    from repro.distributed.sharding import leading_axis_put
    from repro.engine import Engine, EngineConfig
    from repro.graph.coo import from_edges

    rng = np.random.default_rng(seed)
    e = n_dst * deg
    coo = from_edges(rng.integers(0, n_dst, e), rng.integers(0, n_src, e),
                     np.abs(rng.standard_normal(e)).astype(np.float32) + 0.1,
                     n_dst, n_src)
    mesh = make_mesh((n_cores,), ("model",))
    x = leading_axis_put(mesh,
                         rng.standard_normal((n_src, d)).astype(np.float32))

    def arms(topo):
        fn = Engine(EngineConfig(format=base.format, schedule=base.schedule,
                                 topology=topo)) \
            .build(mesh, graph=coo).aggregator()
        gf = jax.jit(jax.grad(lambda xx, fn=fn: jnp.sum(fn(xx) ** 2)))
        return fn, gf

    ref_fwd, ref_bwd = arms("allpairs")

    def paired(f1, f2):
        jax.block_until_ready(f1(x))
        jax.block_until_ready(f2(x))
        rs = []
        for _ in range(n_pairs):
            t0 = time.perf_counter()
            jax.block_until_ready(f1(x))
            t1 = time.perf_counter()
            jax.block_until_ready(f2(x))
            rs.append((t1 - t0) / (time.perf_counter() - t1))
        rs.sort()
        return rs[len(rs) // 2]

    out: Dict = {}
    for topo in topologies:
        if topo == "allpairs":
            continue
        fwd, bwd = arms(topo)
        out[f"agg_fwd_speedup_vs_allpairs_{topo}"] = paired(ref_fwd, fwd)
        out[f"agg_fwdbwd_speedup_vs_allpairs_{topo}"] = paired(ref_bwd, bwd)
    return out


def run_topology_arm(n_cores: int = 4, *, smoke: bool = False,
                     base_spec: str = "ell+pipelined",
                     out_path: str = "BENCH_topology.json") -> Dict:
    """Re-exec the topology sweep under a forced multi-device backend
    (XLA_FLAGS must precede the jax import) and write ``out_path``."""
    from repro.engine import EngineConfig

    base = EngineConfig.from_spec(base_spec)      # fail fast on a bad spec
    kwargs: Dict = {"n_cores": n_cores,
                    "base_spec": f"{base.format}+{base.schedule}"}
    if smoke:
        kwargs.update(batch=128, mid=256, frontier=512, feat=64, hidden=64,
                      deg=8, n_steps=3)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_topologies;"
        f"print(json.dumps(measured_topologies(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"topology arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## topology sweep ({n_cores} simulated cores, "
          f"{rec['base_spec']}+<topology>): one bit-matching stream")
    print("topology,steps,bytes/core,max_step_rows,s_per_step,"
          "predicted_s_per_step")
    for topo in rec["topologies"]:
        pred = rec.get(f"predicted_s_per_step_{topo}")
        print(f"{topo},{rec[f'exchange_steps_{topo}']},"
              f"{rec[f'exchange_bytes_per_core_{topo}']},"
              f"{rec[f'max_step_rows_{topo}']},"
              f"{rec[f's_per_step_{topo}']:.4f},"
              + ("-" if pred is None else f"{pred:.4f}"))
    for topo in rec["topologies"]:
        if topo == "allpairs":
            continue
        print(f"# {topo} vs allpairs: train-step "
              f"{rec[f'step_speedup_vs_allpairs_{topo}']:.3f}x  agg fwd "
              f"{rec[f'agg_fwd_speedup_vs_allpairs_{topo}']:.3f}x  fwd+bwd "
              f"{rec[f'agg_fwdbwd_speedup_vs_allpairs_{topo}']:.3f}x  "
              "(paired median)")
    print(f"# loss_match(<=1e-5 across topologies)={rec['loss_match']}  "
          f"hypercube_vs_allpairs={rec['hypercube_vs_allpairs_speedup']:.3f}x")
    print(f"# (wrote {out_path})")
    return rec


# ---------------------------------------------------------------------------
# --auto: the planner's Engine("auto") arm vs the best measured manual arm.
# ---------------------------------------------------------------------------
def measured_auto(n_cores: int = 4, batch: int = 256, mid: int = 512,
                  frontier: int = 1024, feat: int = 128, hidden: int = 128,
                  deg: int = 8, n_steps: int = 3, n_trials: int = 8,
                  seed: int = 0) -> Dict:
    """``Engine("auto")`` end-to-end: autotune every candidate spec on one
    synthetic stream, persist the winner to ``BENCH_planner.json``, then
    race a fresh ``Engine("auto")`` bundle (which must resolve through the
    persisted record) against the best manual arm, paired per trial.

    The auto bundle rides the SAME resolved spec as the winner, so its
    losses must bit-match the manual arm's and the paired-median ratio
    must sit near 1.0 — ``run.py --smoke`` gates
    ``auto_vs_best_manual_speedup >= 0.9`` (auto never loses the planner's
    own pick by >10%) plus ``auto_loss_match`` and
    ``resolved_matches_winner``.
    """
    from repro.distributed.gcn_train import init_params
    from repro.engine import Engine, EngineConfig, planner

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    stats = planner.GraphStats(n_dst=mid, n_src=frontier,
                               avg_deg=float(deg), feat_dim=feat)
    entry = planner.autotune(stats, n_cores=n_cores, n_steps=n_steps,
                             n_trials=n_trials, seed=seed, force=True)
    resolved = planner.resolve_spec(n_cores=n_cores, graph_stats=stats)

    def canon(s):
        return EngineConfig.from_spec(s).spec

    out: Dict = {"n_cores": n_cores, "backend": jax.default_backend(),
                 "bucket": entry["bucket"], "batch": batch, "mid": mid,
                 "frontier": frontier, "feat": feat, "hidden": hidden,
                 "deg": deg, "n_steps": n_steps, "n_trials": n_trials,
                 "best_manual_spec": canon(entry["spec"]),
                 "resolved_spec": canon(resolved),
                 "resolved_matches_winner":
                     canon(resolved) == canon(entry["spec"]),
                 "autotune_s_per_step": entry["s_per_step"]}
    mesh = make_mesh((n_cores,), ("model",))
    layers = _synthetic_layers(batch, mid, frontier, deg, seed)
    runs = {}
    for name, spec in (("manual", entry["spec"]), ("auto", "auto")):
        bundle = Engine(EngineConfig.from_spec(spec, lr=0.05)).build(mesh)
        b = _synthetic_sharded_batch(bundle, batch, frontier, feat,
                                     layers=layers, seed=seed)
        params = init_params(jax.random.PRNGKey(seed),
                             [(feat, hidden), (hidden, 16)])
        step = bundle.train_step_fn(b["dims"])
        params, loss = step(params, b)        # compile; loss at init params
        first = float(loss)
        params, loss = step(params, b)        # warmup
        jax.block_until_ready(loss)
        runs[name] = {"step": step, "batch": b, "params": params,
                      "loss": first, "times": [], "spec": bundle.spec}
    out["auto_built_spec"] = runs["auto"]["spec"]
    for _ in range(n_trials):
        for arm in runs.values():     # back-to-back: load is common-mode
            t0 = time.perf_counter()
            params, loss = arm["params"], None
            for _ in range(n_steps):
                params, loss = arm["step"](params, arm["batch"])
            jax.block_until_ready(loss)
            arm["times"].append((time.perf_counter() - t0) / n_steps)
    ratios = sorted(m / a for m, a in zip(runs["manual"]["times"],
                                          runs["auto"]["times"]))
    out["s_per_step_manual"] = min(runs["manual"]["times"])
    out["s_per_step_auto"] = min(runs["auto"]["times"])
    out["auto_vs_best_manual_speedup"] = ratios[len(ratios) // 2]
    # same resolved spec on the same stream: losses must be bit-equal
    out["auto_loss_match"] = runs["auto"]["loss"] == runs["manual"]["loss"]
    return out


def run_auto_arm(n_cores: int = 4, *, smoke: bool = False,
                 out_path: str = "BENCH_auto.json") -> Dict:
    """Re-exec the auto-arm measurement under a forced multi-device
    backend and write ``out_path`` (``BENCH_planner.json`` lands in the
    CWD as a side effect — the persisted autotune winner)."""
    kwargs: Dict = {"n_cores": n_cores}
    if smoke:
        kwargs.update(batch=128, mid=256, frontier=512, feat=64, hidden=64,
                      deg=8, n_steps=3, n_trials=4)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_auto;"
        f"print(json.dumps(measured_auto(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"auto arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## auto arm ({n_cores} simulated cores): Engine('auto') vs the "
          "best manual spec")
    print("spec,s_per_step (autotune medians)")
    for spec, s in sorted(rec["autotune_s_per_step"].items(),
                          key=lambda kv: kv[1]):
        print(f"{spec},{s:.4f}")
    print(f"# winner={rec['best_manual_spec']}  "
          f"resolved={rec['resolved_spec']}  "
          f"matches={rec['resolved_matches_winner']}")
    print(f"# auto vs best manual: "
          f"{rec['auto_vs_best_manual_speedup']:.3f}x (paired median, "
          f"gate >= 0.9)  loss bit-match={rec['auto_loss_match']}")
    print(f"# (wrote {out_path}; planner record in BENCH_planner.json)")
    return rec


# ---------------------------------------------------------------------------
# --input-pipeline: host-stall per step, sync vs prefetch (the Trainer's
# async input pipeline), same stream, same spec — the overlap win recorded.
# ---------------------------------------------------------------------------
def measured_input_pipeline(n_cores: int = 4, spec: str = "ell+pipelined",
                            dataset: str = "flickr", scale: float = 0.004,
                            feat: int = 32, hidden: int = 32,
                            batch: int = 32, steps: int = 8,
                            warmup: int = 3, pad_multiple: int = 64,
                            seed: int = 0,
                            modes=("sync", "prefetch")) -> Dict:
    """Per-step host-stall time of the engine-native Trainer under each
    input pipeline.  ``sync`` pays sampling + feature gather + per-batch
    layout build + placement inline on the step path; ``prefetch`` runs
    the identical work as the Trainer's staged chain (each stage on its
    own thread, depth-2 queues), so its stall is only the queue wait the
    device step failed to hide.  Both
    modes consume the SAME deterministic batch stream (seeded pipeline),
    so their loss trajectories must match bit-for-bit — recorded as
    ``input_loss_match``.  Warmup steps absorb the jit compiles (shape
    signatures are coarsened via ``pad_multiple``) and prefill the queue;
    stall counters reset before the measured window.
    """
    from repro.launch.trainer import Trainer

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    out: Dict = {"n_cores": n_cores, "spec": spec, "dataset": dataset,
                 "batch": batch, "steps": steps, "modes": list(modes)}
    losses = {}
    for mode in modes:
        tr = Trainer(spec, dataset, n_cores=n_cores, scale=scale,
                     feat_dim=feat, hidden=hidden, batch_size=batch,
                     lr=0.05, seed=seed, input_pipeline=mode,
                     pad_multiple=pad_multiple, val_batches=0)
        try:
            tr.train_steps(warmup)        # compile + queue prefill
            tr.reset_stall_stats()
            t0 = time.perf_counter()
            losses[mode] = tr.train_steps(steps)
            dt = time.perf_counter() - t0
            out[f"host_stall_s_per_step_{mode}"] = tr.stall_per_step
            out[f"s_per_step_{mode}"] = dt / steps
        finally:
            tr.close()
    if len(losses) == 2:
        a, b = (losses[m] for m in modes)
        out["input_loss_match"] = bool(
            max(abs(x - y) for x, y in zip(a, b)) == 0.0)
        stall_s = out["host_stall_s_per_step_sync"]
        stall_p = out["host_stall_s_per_step_prefetch"]
        out["stall_reduction"] = stall_s / max(stall_p, 1e-9)
        out["prefetch_reduces_stall"] = bool(stall_p < stall_s)
    return out


def run_input_pipeline_arm(n_cores: int = 4, *, smoke: bool = False,
                           spec: str = "ell+pipelined",
                           modes=("sync", "prefetch"),
                           out_path: str = "BENCH_input_pipeline.json"
                           ) -> Dict:
    """Re-exec the input-pipeline measurement under a forced multi-device
    backend and write ``out_path`` (same child-process pattern as
    :func:`run_overlap_arm`: XLA_FLAGS must precede the jax import)."""
    kwargs = {"n_cores": n_cores, "spec": spec, "modes": tuple(modes)}
    if smoke:
        kwargs.update(scale=0.003, feat=32, hidden=32, batch=32, steps=6,
                      warmup=2)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_input_pipeline;"
        f"print(json.dumps(measured_input_pipeline(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"input-pipeline arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## input pipeline ({n_cores} simulated cores, {spec}): "
          "host-stall per step, sync vs prefetch")
    print("mode,host_stall_s_per_step,s_per_step")
    for mode in rec["modes"]:
        print(f"{mode},{rec[f'host_stall_s_per_step_{mode}']:.4f},"
              f"{rec[f's_per_step_{mode}']:.4f}")
    if "stall_reduction" in rec:
        print(f"# prefetch cuts host stall {rec['stall_reduction']:.1f}x "
              f"(strictly less: {rec['prefetch_reduces_stall']}, "
              f"loss bit-match: {rec['input_loss_match']})")
    print(f"# (wrote {out_path})")
    return rec


# ---------------------------------------------------------------------------
# --feature-store: device-resident vs out-of-core features, one bit-matching
# stream — host-stall, gather traffic, and hot-vertex cache hit rate.
# ---------------------------------------------------------------------------
def measured_feature_store(n_cores: int = 4, spec: str = "ell+pipelined",
                           dataset: str = "flickr", scale: float = 0.004,
                           feat: int = 32, hidden: int = 32,
                           batch: int = 32, steps: int = 8,
                           warmup: int = 3, pad_multiple: int = 64,
                           seed: int = 0, cache_capacity: int = 64,
                           modes=("device", "host", "mmap")) -> Dict:
    """The Trainer on each feature residency mode, sync vs staged prefetch.

    ``device`` is the dense in-memory baseline; ``host``/``mmap`` are
    registered :mod:`repro.featurestore` backends with a hot-vertex cache
    in front.  Every mode consumes the SAME deterministic batch stream
    (store-backed :func:`make_dataset` generation is bit-identical to the
    dense path at the same seed), so all loss trajectories must bit-match
    — recorded as ``loss_match``.  Per store mode it records the sync
    host-stall (gather + layout + placement inline on the step path), the
    staged-prefetch stall (sample → gather → layout → place, each stage on
    its own thread — only the queue wait the device step failed to hide),
    the store bytes actually gathered in the measured window, and the
    cache hit rate.  Headline keys (``stall_reduction``,
    ``cache_hit_rate``, ``prefetch_reduces_stall``) come from the mmap
    mode — the tier where a synchronous gather would pay disk latency on
    the critical path.
    """
    from repro.launch.trainer import Trainer

    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices, have {len(jax.devices())} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    out: Dict = {"n_cores": n_cores, "spec": spec, "dataset": dataset,
                 "batch": batch, "steps": steps, "modes": list(modes),
                 "cache_capacity": cache_capacity}
    ref_losses = None
    out["loss_match"] = True
    for mode in modes:
        ds = make_dataset(dataset, scale=scale, feat_dim=feat,
                          features="dense" if mode == "device" else mode)
        cap = 0 if mode == "device" else cache_capacity
        try:
            for pipe in ("sync", "prefetch"):
                tr = Trainer(spec, ds, n_cores=n_cores, hidden=hidden,
                             batch_size=batch, lr=0.05, seed=seed,
                             input_pipeline=pipe,
                             pad_multiple=pad_multiple, val_batches=0,
                             cache_capacity=cap)
                try:
                    tr.train_steps(warmup)    # compile + queue prefill
                    tr.reset_stall_stats()
                    if tr.cache is not None:
                        tr.cache.reset_stats()
                    g0 = tr.store.bytes_gathered if tr.store else 0
                    t0 = time.perf_counter()
                    losses = tr.train_steps(steps)
                    dt = time.perf_counter() - t0
                    out[f"host_stall_s_per_step_{mode}_{pipe}"] = \
                        tr.stall_per_step
                    out[f"s_per_step_{mode}_{pipe}"] = dt / steps
                    if tr.store is not None and pipe == "prefetch":
                        # window delta: in-flight prefetched batches blur
                        # the edges, but over the measured steps this is
                        # the steady-state store traffic
                        out[f"gather_bytes_{mode}"] = \
                            int(tr.store.bytes_gathered - g0)
                        if tr.cache is not None:
                            out[f"cache_hit_rate_{mode}"] = \
                                tr.cache.hit_rate
                finally:
                    tr.close()
                if ref_losses is None:
                    ref_losses = losses
                elif max(abs(a - b)
                         for a, b in zip(ref_losses, losses)) != 0.0:
                    out["loss_match"] = False
        finally:
            if mode != "device":
                ds.features.close()     # mmap: unlink the tempfile
        if mode != "device":
            ss = out[f"host_stall_s_per_step_{mode}_sync"]
            sp = out[f"host_stall_s_per_step_{mode}_prefetch"]
            out[f"stall_reduction_{mode}"] = ss / max(sp, 1e-9)
            out[f"prefetch_reduces_stall_{mode}"] = bool(sp < ss)
    head = "mmap" if "mmap" in modes \
        else next((m for m in modes if m != "device"), None)
    if head is not None:
        out["headline_mode"] = head
        out["stall_reduction"] = out[f"stall_reduction_{head}"]
        out["prefetch_reduces_stall"] = out[f"prefetch_reduces_stall_{head}"]
        out["cache_hit_rate"] = out.get(f"cache_hit_rate_{head}", 0.0)
    return out


def run_feature_store_arm(n_cores: int = 4, *, smoke: bool = False,
                          spec: str = "ell+pipelined",
                          out_path: str = "BENCH_feature_store.json"
                          ) -> Dict:
    """Re-exec the feature-store measurement under a forced multi-device
    backend and write ``out_path`` (same child-process pattern as
    :func:`run_overlap_arm`: XLA_FLAGS must precede the jax import)."""
    kwargs: Dict = {"n_cores": n_cores, "spec": spec}
    if smoke:
        kwargs.update(scale=0.003, feat=32, hidden=32, batch=32, steps=6,
                      warmup=2, cache_capacity=64)
    child = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks.epoch_time import measured_feature_store;"
        f"print(json.dumps(measured_feature_store(**{kwargs!r})))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_host_env(n_cores, os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", child], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"feature-store arm failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"## feature store ({n_cores} simulated cores, {spec}): "
          "device vs out-of-core, sync vs staged prefetch")
    print("mode,pipeline,host_stall_s_per_step,s_per_step")
    for mode in rec["modes"]:
        for pipe in ("sync", "prefetch"):
            print(f"{mode},{pipe},"
                  f"{rec[f'host_stall_s_per_step_{mode}_{pipe}']:.4f},"
                  f"{rec[f's_per_step_{mode}_{pipe}']:.4f}")
    for mode in rec["modes"]:
        if mode == "device":
            continue
        hr = rec.get(f"cache_hit_rate_{mode}")
        print(f"# {mode}: staged prefetch cuts host stall "
              f"{rec[f'stall_reduction_{mode}']:.1f}x (strictly less: "
              f"{rec[f'prefetch_reduces_stall_{mode}']})  gather "
              f"{rec[f'gather_bytes_{mode}'] / 1e6:.2f} MB"
              + ("" if hr is None else f"  cache hit-rate {hr:.2f}"))
    print(f"# loss bit-match across all modes: {rec['loss_match']}")
    print(f"# (wrote {out_path})")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--overlap", action="store_true",
                    help="measure the engine arms' step time vs the "
                         "coo+serial oracle")
    ap.add_argument("--input-pipeline", choices=["sync", "prefetch", "both"],
                    default=None,
                    help="measure the Trainer's per-step host-stall under "
                         "the given input pipeline(s); 'both' records the "
                         "sync-vs-prefetch overlap win")
    ap.add_argument("--spec", default="ell+pipelined",
                    help="engine spec for --input-pipeline")
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes (CI): implies a quick --overlap run")
    ap.add_argument("--cores", type=int, default=8,
                    help="simulated device count for the arm measurement")
    ap.add_argument("--arms", default=",".join(DEFAULT_ARMS),
                    help="comma-separated engine specs to measure against "
                         "the coo+serial oracle (replaces the old "
                         "--ell/--no-ell flag pair)")
    ap.add_argument("--feature-store", action="store_true",
                    help="measure feature residency (device vs host vs "
                         "mmap store) under sync vs staged-prefetch input "
                         "pipelines (writes BENCH_feature_store.json)")
    ap.add_argument("--topologies", action="store_true",
                    help="sweep every registered interconnect topology on "
                         "one bit-matching stream (exchange steps + bytes "
                         "+ measured speedups vs the allpairs reference; "
                         "writes BENCH_topology.json)")
    ap.add_argument("--auto", action="store_true",
                    help="autotune every spec, persist the winner to "
                         "BENCH_planner.json, and race Engine('auto') "
                         "against the best manual arm (writes "
                         "BENCH_auto.json)")
    ap.add_argument("--redundancy", action="store_true",
                    help="race the GraphACT-merged ELL engine "
                         "(merge=redundancy + mincom partitioning) against "
                         "the plain ELL arm on one bit-matching power-law "
                         "stream (writes BENCH_redundancy.json)")
    args = ap.parse_args()

    ran = False
    if args.overlap or args.smoke:
        arms = tuple(s for s in args.arms.split(",") if s)
        run_overlap_arm(args.cores, smoke=args.smoke, arms=arms)
        ran = True
    if args.topologies:
        run_topology_arm(min(args.cores, 4) if args.smoke else args.cores,
                         smoke=args.smoke, base_spec=args.spec)
        ran = True
    if args.auto:
        run_auto_arm(min(args.cores, 4) if args.smoke else args.cores,
                     smoke=args.smoke)
        ran = True
    if args.redundancy:
        run_redundancy_arm(min(args.cores, 4) if args.smoke else args.cores,
                           smoke=args.smoke)
        ran = True
    if args.feature_store:
        run_feature_store_arm(min(args.cores, 4) if args.smoke
                              else args.cores,
                              smoke=args.smoke, spec=args.spec)
        ran = True
    if args.input_pipeline is not None:
        modes = ("sync", "prefetch") if args.input_pipeline == "both" \
            else (args.input_pipeline,)
        run_input_pipeline_arm(args.cores, smoke=args.smoke,
                               spec=args.spec, modes=modes)
        ran = True
    if not ran:
        _table2_main()


def _table2_main() -> None:
    print("## analytic (paper scale, dataflow component of Table 2)")
    print("dataset,ops_naive_tab1,ops_naive_realistic,ops_ours,"
          "speedup_tab1,speedup_realistic")
    for r in analytic_epoch_ratio():
        print(f"{r['dataset']},{r['ops_naive']:.4g},"
              f"{r['ops_naive_realistic']:.4g},{r['ops_ours']:.4g},"
              f"{r['speedup_paper_literal']:.2f},{r['speedup']:.3f}")
    print("# paper Table 2 overall speedup vs HP-GNN: 1.03x-1.81x "
          "(dataflow + NoC components combined)")
    print("## measured (reduced scale, s/batch on CPU)")
    print("dataset,s_naive,s_ours,speedup")
    for name in ("flickr", "reddit"):
        m = measured_epoch(name)
        print(f"{name},{m['naive']:.4f},{m['ours']:.4f},{m['speedup']:.3f}")


if __name__ == "__main__":
    main()
