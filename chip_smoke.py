#!/usr/bin/env python3
"""Chip smoke: the GCN trainer's main path on a TPU, checked against a
plain float32 ``jax.numpy`` reference.

    python chip_smoke.py              # one chip: kernel parity + training
    python chip_smoke.py --chips 4    # four chips: hypercube training only

One chip runs two phases:

(a) **Kernel parity** — ``repro.kernels.ops.ell_aggregate`` forward and
    gradient through the native Pallas ELL kernel (its ``tpu_custom_call``
    must be in the compiled HLO), on a power-law graph whose plan holds
    degree buckets of K ≥ 512 in both directions, against dense ``A @ x``
    and ``Aᵀ g`` at ``precision="highest"``.
(b) **Training** — ``Trainer("ell+pipelined", "flickr", n_cores=1)`` at the
    paper's §5.1 widths (features 500, hidden 256, 7 classes, batch 1024,
    fanouts ``configs.gcn_paper.FANOUTS``) on the full synthetic Flickr
    graph, for ``STEPS`` steps.  Against a plain float32 segment-sum GCN
    on the same first batch and initial params: the logits, the first loss
    and the first SGD update (so the backward too) must match; every loss
    must be finite.

``--chips 4`` runs only the training phase at ``n_cores=4`` (hypercube)
and at ``n_cores=1`` on the same batch stream: each against the
reference as above, and the 4-core losses and parameters after ``STEPS``
steps against the 1-core ones.

The spec is pinned (``"auto"`` is never resolved) and the kernel tiles come
from ``repro.kernels.tune.DEFAULTS["tpu"]``: no record file is read.  The
script exits nonzero, printing no result, when JAX finds no TPU or any
check fails.  Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

SPEC = "ell+pipelined"
SEED = 0
STEPS = 5
WARM_REPS = 3             # timed re-runs of step 0's compiled program
DATASET_SCALE = 1.0       # full synthetic Flickr
PAD_MULTIPLE = 1024       # coarse sampler padding: layer dims repeat
# kernel parity graph: power-law, hub buckets of K >= MIN_HUB_K both ways
KERNEL_N, KERNEL_AVG_DEG, KERNEL_D, MIN_HUB_K = 4096, 16, 256, 512

# Tolerances.  Every matmul runs at full f32 (``kernels.spmm.F32_MATMUL``),
# so the engine and the reference differ only in summation order.  Sound
# one-chip readings (TPU v5e): kernel rel. error 3.6e-7 / 5.6e-7, logits
# 1.7e-7, first loss |diff| 0 to 9.5e-7, first update 2.3e-7.  Faults read:
# a bf16-pass kernel 2.5e-3 on the kernel check; a bf16-pass combination
# matmul 3.9e-3 on the logits and 6.1e-2 on the update (loss |diff| 9.5e-7:
# the loss alone cannot see it); a zeroed aggregation 1.0.
#: max |kernel − dense| / max |dense|, forward and gradient.
KERNEL_RTOL = 1e-5
#: max |logits − reference| / max |reference| on the first batch.
LOGIT_RTOL = 1e-5
#: |loss − reference| on the first batch; 4-core vs 1-core losses per step.
LOSS_TOL = 1e-5
#: per layer, max |update − reference update| / max |reference update|
#: (looser: the backward adds reductions, and no 4-core reading preceded it),
#: where an update is ``params0 − params_after``, beyond the float32
#: rounding of the params (``update_rel``): the first SGD step vs
#: ``lr · ∇loss`` of the reference, and 4-core vs 1-core after STEPS steps.
UPDATE_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def verdict(good: bool) -> str:
    return "ok" if good else "FAIL"


class CompileCounter:
    """Backend compiles, their seconds and persistent-cache hits, from
    JAX's monitoring events."""

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


# ---------------------------------------------------------------------------
# (a) kernel parity
# ---------------------------------------------------------------------------
def powerlaw_coo(n: int, avg_deg: int, seed: int):
    """Chung-Lu style graph: rows and columns both drawn ∝ (i+1)^-0.8 over
    independent permutations, so a few rows and a few columns are hubs."""
    import numpy as np

    from repro.graph.coo import from_edges

    rng = np.random.default_rng(seed)
    w = (np.arange(n) + 1.0) ** -0.8
    p = w / w.sum()
    e = n * avg_deg
    rows = rng.permutation(n)[rng.choice(n, e, p=p)]
    cols = rng.permutation(n)[rng.choice(n, e, p=p)]
    vals = rng.uniform(0.1, 1.0, e).astype(np.float32)
    return from_edges(rows, cols, vals, n, n)


def kernel_parity() -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import edgeplan
    from repro.kernels.ops import ell_aggregate

    n, d = KERNEL_N, KERNEL_D
    coo = powerlaw_coo(n, KERNEL_AVG_DEG, SEED)
    tables = edgeplan.build_plan(coo, caps="pow2").device_tables()
    k_fwd = max(c.shape[1] for c in tables["cols"])
    k_bwd = max(c.shape[1] for c in tables["t_cols"])
    log(f"kernel: graph n={n} nnz={coo.nnz} d={d}  widest bucket "
        f"fwd K={k_fwd} bwd K={k_bwd}")
    if min(k_fwd, k_bwd) < MIN_HUB_K:
        log(f"kernel: FAIL no bucket of K >= {MIN_HUB_K} in both directions")
        return False
    rng = np.random.default_rng(SEED + 1)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)

    fwd = jax.jit(lambda xx: ell_aggregate(tables, xx))
    grad = jax.jit(lambda xx: jax.grad(
        lambda v: jnp.vdot(ell_aggregate(tables, v), g))(xx))
    for name, f in (("fwd", fwd), ("grad", grad)):
        hlo = f.lower(x).compile().as_text()
        if "tpu_custom_call" not in hlo:
            log(f"kernel: FAIL {name} has no Pallas kernel in its HLO")
            return False
    y, dx = fwd(x), grad(x)

    a = np.zeros((n, n), np.float32)
    np.add.at(a, (np.asarray(coo.rows), np.asarray(coo.cols)),
              np.asarray(coo.vals))
    a = jnp.asarray(a)
    with jax.default_matmul_precision("highest"):
        y_ref = a @ x
        dx_ref = a.T @ g
    ok = True
    for name, got, want in (("fwd A@x", y, y_ref), ("grad A^T g", dx, dx_ref)):
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        rel = err / scale
        good = rel <= KERNEL_RTOL
        ok &= good
        log(f"kernel: {name:10s} max|err|={err:.3e} max|ref|={scale:.3e} "
            f"rel={rel:.3e} (tol {KERNEL_RTOL:g}) {verdict(good)}")
    return ok


# ---------------------------------------------------------------------------
# (b) training
# ---------------------------------------------------------------------------
def reference_logits(params, layers, feats):
    """Plain float32 GCN forward: per hop, deepest first,
    ``h = segment_sum(vals · (h @ W)[cols], rows)``, ReLU between hops —
    the engine's CoAg order, written without any of its layouts."""
    import jax
    import jax.numpy as jnp

    h = jnp.asarray(feats, jnp.float32)
    n_layers = len(layers)
    for l in range(n_layers - 1, -1, -1):
        coo = layers[l]
        h = jnp.dot(h, params[n_layers - 1 - l]["w"],
                    precision=jax.lax.Precision.HIGHEST)
        msg = jnp.asarray(coo.vals)[:, None] * h[jnp.asarray(coo.cols)]
        h = jax.ops.segment_sum(msg, jnp.asarray(coo.rows),
                                num_segments=coo.n_dst)
        if l:
            h = jnp.maximum(h, 0.0)
    return h


def reference(params, layers, feats, labels):
    """(logits, mean cross-entropy loss, ∇loss wrt params) on one batch."""
    import jax
    import jax.numpy as jnp

    def loss_fn(p):
        logits = reference_logits(p, layers, feats)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, jnp.asarray(labels)[:, None], -1)
        return nll.mean(), logits

    (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return logits, float(loss), grads


def update_rel(params_after, params0, want_update, roundings: int) -> float:
    """Largest, over layers, of max |(params0 − params_after) − want| /
    max |want|, in float64 on the host (the subtraction is exact).  Each
    SGD step rounds the params to float32 once, off by at most half an ulp
    of the params, which can be a sizeable part of a small update; so each
    element may miss by ``roundings`` half-ulps before the excess counts."""
    import numpy as np

    worst = 0.0
    for pa, p0, u in zip(params_after, params0, want_update):
        pa, p0 = np.asarray(pa["w"]), np.asarray(p0["w"])
        got = p0.astype(np.float64) - pa.astype(np.float64)
        want = np.asarray(u["w"], np.float64)
        half_ulp = 0.5 * np.maximum(np.spacing(np.abs(p0)),
                                    np.spacing(np.abs(pa))).astype(np.float64)
        excess = np.maximum(np.abs(got - want) - roundings * half_ulp, 0.0)
        worst = max(worst, float(np.max(excess) / np.max(np.abs(want))))
    return worst


def first_batch(dataset, sampler):
    """The trainer's first batch, drawn again: the stream is keyed by
    (seed, epoch, batch index) alone."""
    from repro.configs.gcn_paper import BATCH
    from repro.data import GraphBatchPipeline

    return next(GraphBatchPipeline(dataset, sampler, BATCH, seed=SEED))


def train(dataset, n_cores: int, counter: CompileCounter) -> dict:
    """``STEPS`` Trainer steps (losses, params, seconds and compiles per
    step), then, on the first batch and the initial params, the engine's
    logits and ``WARM_REPS`` timed re-runs of step 0's compiled program —
    every training step compiles its own program, so these are the only
    warm step times."""
    import jax
    import numpy as np

    from repro.configs.gcn_paper import BATCH, FANOUTS, HIDDEN
    from repro.launch.trainer import Trainer

    tr = Trainer(SPEC, dataset, n_cores=n_cores, hidden=HIDDEN,
                 batch_size=BATCH, fanouts=FANOUTS,
                 pad_multiple=PAD_MULTIPLE, seed=SEED)
    out = {"params0": tr.params, "lr": tr.engine.config.lr, "losses": [],
           "params": [], "secs": [], "compiles": []}
    try:
        for _ in range(STEPS):
            c0 = counter.snapshot()[0]
            t0 = time.perf_counter()
            out["losses"] += tr.train_steps(1)   # float(loss): waits
            out["secs"].append(time.perf_counter() - t0)
            out["compiles"].append(counter.snapshot()[0] - c0)
            out["params"].append(tr.params)
    finally:
        tr.close()
    out["batch0"] = first_batch(dataset, tr.sampler)
    batch = tr.bundle.shard_batch(*out["batch0"])
    out["logits0"] = np.asarray(tr.bundle.forward(out["params0"], batch))
    c0 = counter.snapshot()[0]
    out["warm"] = []
    for _ in range(WARM_REPS):
        t0 = time.perf_counter()
        _, loss = jax.block_until_ready(
            tr.bundle.train_step(out["params0"], batch))
        out["warm"].append(time.perf_counter() - t0)
        out["warm_loss"] = float(loss)
    out["warm_compiles"] = counter.snapshot()[0] - c0
    return out


def report_steps(tag: str, run: dict) -> None:
    for i, (l, s, c) in enumerate(zip(run["losses"], run["secs"],
                                      run["compiles"])):
        log(f"{tag}: step {i} loss {l!r} time {s:.4f}s compiles {c}")
    log(f"{tag}: warm step 0 re-runs {[round(s, 4) for s in run['warm']]} s"
        f" (compiles {run['warm_compiles']}, loss {run['warm_loss']!r})")


def check_vs_reference(tag: str, run: dict) -> bool:
    """The run's first batch against the plain reference: logits, loss,
    and the first SGD update; and every loss finite."""
    import jax
    import numpy as np

    mb, feats, labels = run["batch0"]
    logits, loss, grads = reference(run["params0"], mb.layers, feats, labels)
    logits = np.asarray(logits)
    ok = True

    rel = float(np.max(np.abs(run["logits0"] - logits))
                / np.max(np.abs(logits)))
    good = rel <= LOGIT_RTOL
    ok &= good
    log(f"{tag}: first-batch logits {run['logits0'].shape} rel err vs "
        f"reference {rel:.3e} (tol {LOGIT_RTOL:g}) {verdict(good)}")

    d = abs(run["losses"][0] - loss)
    good = d <= LOSS_TOL
    ok &= good
    log(f"{tag}: first loss {run['losses'][0]!r} reference {loss!r} "
        f"|diff|={d:.3e} (tol {LOSS_TOL:g}) {verdict(good)}")

    want = jax.tree_util.tree_map(lambda g: run["lr"] * np.asarray(g), grads)
    rel = update_rel(run["params"][0], run["params0"], want, roundings=1)
    good = rel <= UPDATE_RTOL
    ok &= good
    log(f"{tag}: first update vs reference lr*grad: worst layer rel err "
        f"{rel:.3e} (tol {UPDATE_RTOL:g}) {verdict(good)}")

    if not np.all(np.isfinite(run["losses"])):
        ok = False
        log(f"{tag}: FAIL non-finite loss {run['losses']}")
    return ok


def check_vs_one_core(tag: str, run: dict, base: dict) -> bool:
    """A multi-core run against the 1-core run on the same batch stream:
    per-step losses, and the parameters after the last step."""
    import numpy as np

    d = float(np.max(np.abs(np.asarray(run["losses"])
                            - np.asarray(base["losses"]))))
    good_loss = d <= LOSS_TOL
    log(f"{tag} vs n_cores=1: max |loss diff| over {STEPS} steps {d:.3e} "
        f"(tol {LOSS_TOL:g}) {verdict(good_loss)}")
    want = [{"w": np.asarray(p0["w"], np.float64)
             - np.asarray(p1["w"], np.float64)}
            for p0, p1 in zip(base["params0"], base["params"][-1])]
    rel = update_rel(run["params"][-1], run["params0"], want,
                     roundings=2 * STEPS)
    good_params = rel <= UPDATE_RTOL
    log(f"{tag} vs n_cores=1: params after {STEPS} steps, worst layer rel "
        f"err of the update {rel:.3e} (tol {UPDATE_RTOL:g}) "
        f"{verdict(good_params)}")
    return good_loss and good_params


def training(n_cores_list, counter: CompileCounter) -> bool:
    from repro.configs.gcn_paper import BATCH, FANOUTS, HIDDEN
    from repro.graph import make_dataset

    t0 = time.perf_counter()
    ds = make_dataset("flickr", scale=DATASET_SCALE, seed=SEED)
    log(f"train: flickr nodes={ds.graph.n_nodes} "
        f"csr_entries={ds.graph.n_edges} feat={ds.features.shape[1]} "
        f"classes={ds.stats.n_classes} hidden={HIDDEN} batch={BATCH} "
        f"fanouts={tuple(FANOUTS)} spec={SPEC} "
        f"({time.perf_counter() - t0:.1f}s to build)")
    runs = {}
    for p in n_cores_list:
        n0, s0, h0 = counter.snapshot()
        run = train(ds, p, counter)
        n1, s1, h1 = counter.snapshot()
        tag = f"train[n_cores={p}]"
        report_steps(tag, run)
        log(f"{tag}: compiles {n1 - n0} ({s1 - s0:.1f}s, "
            f"persistent-cache hits {h1 - h0})")
        runs[p] = run

    ok = True
    for p, run in runs.items():
        ok &= check_vs_reference(f"train[n_cores={p}]", run)
    for p, run in runs.items():
        if p != 1:
            ok &= check_vs_one_core(f"train[n_cores={p}]", run, runs[1])
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    from repro.kernels import tune

    tune.pin(tune.DEFAULTS["tpu"])
    counter = CompileCounter()
    log(f"device: {devs[0].device_kind} x{len(devs)}  jax {jax.__version__}"
        f"  compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        " entries at start)")

    results = {}
    if args.chips == 1:
        t0 = time.perf_counter()
        results["kernel"] = kernel_parity()
        log(f"kernel: phase {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        results["train"] = training((1,), counter)
        log(f"train: phase {time.perf_counter() - t0:.1f}s")
    else:
        t0 = time.perf_counter()
        results["train4"] = training((4, 1), counter)
        log(f"train4: phase {time.perf_counter() - t0:.1f}s")

    stats = devs[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log("memory: peak device bytes "
        + (f"{peak} ({peak / 2**30:.2f} GiB)" if peak is not None
           else "not reported by this backend"))
    n, secs, hits = counter.snapshot()
    log(f"compiles: {n} total, {secs:.1f}s, persistent-cache hits {hits}")
    failed = [k for k, v in results.items() if not v]
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
