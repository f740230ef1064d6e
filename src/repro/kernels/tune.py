"""Tile/bucket autotuner for the pre-reduced ELL aggregation engine.

A small sweep over the knobs that matter — the (br, bd, bs) kernel tiles
and the degree-bucket capacity scheme (``caps``) of
:mod:`repro.kernels.edgeplan` — timed on a synthetic skewed graph, with the
winner persisted to JSON so every later process (and every training step)
just reads the file.

    from repro.kernels import tune
    cfg = tune.get_config()        # file → env override → backend defaults
    rec = tune.autotune()          # run the sweep, persist, return record

Resolution order of :func:`get_config`:

1. in-process cache (or a config set with :func:`pin`);
2. the JSON file at ``$REPRO_AUTOTUNE_PATH`` (default
   ``BENCH_autotune.json`` in the CWD — benchmarks/CI write and upload it);
3. backend defaults (no implicit sweep: tests and library imports must stay
   hermetic — benchmarks and first-use call :func:`autotune` explicitly).

The file→env→default persistence itself lives in
:class:`repro.engine.plans.RecordStore` — the same contract the spec
planner's ``BENCH_planner.json`` rides — this module keeps only the
ELL-specific parts (backend defaults, the sweep, the record schema).

The bucket-scheme arm times the real consumer (the jitted
``ell_aggregate`` forward+backward) per candidate; the tile arm only runs
where tiles matter (a native TPU backend — interpret-mode timings would
tune the numpy emulator, not the hardware).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

_STORE = None


def _store():
    # lazy: importing repro.engine at module load would cycle back through
    # the formats' kernel imports; by call time everything is registered
    global _STORE
    if _STORE is None:
        from repro.engine.plans import RecordStore
        _STORE = RecordStore(DEFAULT_FILENAME, ENV_PATH)
    return _STORE


DEFAULT_FILENAME = "BENCH_autotune.json"
ENV_PATH = "REPRO_AUTOTUNE_PATH"

# Safe fall-back tiles per backend; caps="pow2" keeps skewed rows from
# inflating everyone's padding even before any sweep has run.
DEFAULTS: Dict[str, Dict] = {
    "tpu": {"br": 128, "bd": 128, "bs": 128, "caps": "pow2"},
    "gpu": {"br": 128, "bd": 128, "bs": 128, "caps": "pow2"},
    "cpu": {"br": 128, "bd": 128, "bs": 128, "caps": "pow2"},
}

CAPS_CANDIDATES = ["pow2", "single", [2, 8, 32]]
TILE_CANDIDATES = [(128, 128, 128), (64, 128, 256), (256, 128, 128),
                   (128, 256, 128)]

_config: Optional[Dict] = None


def cache_path() -> str:
    return _store().path()


def _backend() -> str:
    import jax
    return jax.default_backend()


def get_config() -> Dict:
    """The tuned config (see module docstring for resolution order)."""
    global _config
    if _config is not None:
        return _config
    cfg = dict(DEFAULTS.get(_backend(), DEFAULTS["cpu"]))
    rec = _store().load()             # unreadable/corrupt cache → None
    if rec is not None and rec.get("backend") == _backend():
        try:
            cfg.update(rec.get("config", {}))
        except (ValueError, TypeError):
            pass                      # malformed config block → defaults
    _config = cfg
    return cfg


def pin(cfg: Dict) -> None:
    """Serve ``cfg`` from :func:`get_config` for the rest of this process,
    reading no record file (a run that must not depend on what an earlier
    sweep left on disk pins its tiles)."""
    global _config
    _config = dict(cfg)


def reset() -> None:
    """Drop the in-process cache (tests; after writing a new file)."""
    global _config
    _config = None


def _bench_plan_caps(caps, n: int, deg: int, d: int, n_reps: int,
                     seed: int) -> float:
    """Seconds per fwd+bwd of the jitted ELL aggregate under one scheme."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.graph.coo import from_edges
    from repro.kernels import edgeplan
    from repro.kernels.ops import ell_aggregate

    rng = np.random.default_rng(seed)
    # skewed degrees: a few hubs + a long tail (the case bucketing targets)
    rows = np.concatenate([
        rng.integers(0, n, n * deg),
        rng.integers(0, max(n // 16, 1), n * deg // 2),   # hub rows
    ])
    e = len(rows)
    coo = from_edges(rows, rng.integers(0, n, e),
                     np.abs(rng.standard_normal(e)).astype(np.float32) + 0.1,
                     n, n)
    plan = edgeplan.build_plan(coo, caps=caps)
    tables = plan.device_tables()
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    g = jax.jit(jax.grad(lambda xx: (ell_aggregate(tables, xx) ** 2).sum()))
    jax.block_until_ready(g(x))              # compile
    t0 = time.perf_counter()
    for _ in range(n_reps):
        jax.block_until_ready(g(x))
    return (time.perf_counter() - t0) / n_reps


def _bench_tiles(br: int, bd: int, bs: int, n: int, d: int, n_reps: int,
                 seed: int) -> float:
    """Seconds per native spmm_ell call for one tile triple (TPU only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import spmm_ell

    rng = np.random.default_rng(seed)
    K = 8
    cols = jnp.asarray(rng.integers(0, n, (n, K)), jnp.int32)
    vals = jnp.asarray(np.abs(rng.standard_normal((n, K))), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    out = spmm_ell(cols, vals, x, br=br, bd=bd, bs=bs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n_reps):
        jax.block_until_ready(spmm_ell(cols, vals, x, br=br, bd=bd, bs=bs))
    return (time.perf_counter() - t0) / n_reps


def autotune(path: Optional[str] = None, *, force: bool = False,
             n: int = 512, deg: int = 8, d: int = 64, n_reps: int = 5,
             seed: int = 0) -> Dict:
    """Run the sweep, persist the winner to ``path``, return the record.

    Idempotent per file: an existing record for this backend is returned
    untouched unless ``force`` — so "first use" sweeps once per machine,
    and the training loop never re-tunes.
    """
    path = path or cache_path()
    backend = _backend()
    if not force:
        rec = _store().load(path)
        if rec is not None and rec.get("backend") == backend:
            return rec

    caps_timings: List[Dict] = []
    for caps in CAPS_CANDIDATES:
        s = _bench_plan_caps(caps, n, deg, d, n_reps, seed)
        caps_timings.append({"caps": caps, "s_per_fwdbwd": s})
    best_caps = min(caps_timings, key=lambda r: r["s_per_fwdbwd"])["caps"]

    tile_timings: List[Dict] = []
    best_tiles = tuple(DEFAULTS.get(backend, DEFAULTS["cpu"])[k]
                       for k in ("br", "bd", "bs"))
    if backend == "tpu":              # interpret timings would tune numpy
        for br, bd, bs in TILE_CANDIDATES:
            s = _bench_tiles(br, bd, bs, max(n, 256), max(d, 128), n_reps,
                             seed)
            tile_timings.append({"br": br, "bd": bd, "bs": bs, "s": s})
        best = min(tile_timings, key=lambda r: r["s"])
        best_tiles = (best["br"], best["bd"], best["bs"])

    rec = {
        "backend": backend,
        "config": {"br": best_tiles[0], "bd": best_tiles[1],
                   "bs": best_tiles[2], "caps": best_caps},
        "sweep": {"caps": caps_timings, "tiles": tile_timings,
                  "n": n, "deg": deg, "d": d, "n_reps": n_reps},
    }
    _store().save(rec, path)
    reset()                           # next get_config() sees the new file
    return rec
