#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload gcn-reddit.train --seed 7 \\
        --seconds 20 --trace 0

The cell, its configuration and its traffic are found by name:
``BENCHMARK.json`` names the cell's ``config`` and ``traffic``, whose files
are ``chipbench/configs/<config>.json`` and ``chipbench/traffic/<traffic>
.json``; the configuration's ``model`` names ``chipbench/models/<model>.py``
(its weights, plain reference and work count, see :func:`load_model`); the
limits of its ``correct`` are ``chipbench/limits/<cell>.json``; each metric
is read by ``chipbench/metrics/<metric>.py``.  A new cell, configuration,
model, traffic mix or metric is new files and entries, with no edit here.

A run: set-up (the configuration's data, the program's Trainer, one step on
each batch the window will use, loaded from the persistent cache), then the
measured window of ``--seconds`` with the persistent cache off, then the
program's state freed and its first steps compared with the plain
reference.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.  Where the
cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout)
does not yet hold this cell's programs for this code (no stamp, see
:func:`prime_stamp`), set-up first runs a child process that builds them
and exits, so that no run reads compile-time device memory into
``peak_hbm_gib``.  The last line of stdout is one JSON object; the last
lines of stderr are the numbers compared, each with its limit.  With no TPU, or fewer chips than
the cell asks for, the run exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.metadata  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # the repository root, not this directory, heads the path: the harness
    # imports itself as the ``chipbench`` package, and ``trace`` is also
    # the name of a standard module
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

BENCH = os.path.join(ROOT, "chipbench")
#: where :func:`load_model` finds a configuration's model
MODELS = os.path.join(BENCH, "models")
#: what a model module provides (see :func:`load_model`)
MODEL_FUNCTIONS = ("init", "to_program", "from_program", "trainer_kwargs",
                   "loss_and_grad", "output_leaves", "step_work")
#: JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a fixed place in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run writes its profile (emptied first)
TRACE_DIR = os.path.join(ROOT, ".chipbench_out", "trace")
#: the host span around each window step
STEP_SPAN = "bench.step"


def cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else :data:`CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def prime_stamp(workload: str) -> str:
    """The file that says the cache holds ``workload``'s programs: keyed by
    the checkout's path, the cache's, JAX's version and every source file
    the programs are built from, since each of these changes the programs
    or their cache keys."""
    h = hashlib.sha256()
    for part in (ROOT, cache_dir(), importlib.metadata.version("jax"),
                 importlib.metadata.version("jaxlib")):
        h.update(part.encode() + b"\0")
    for top in (BENCH, os.path.join(ROOT, "src")):
        for dirpath, dirs, names in os.walk(top):
            dirs.sort()
            for name in sorted(names):
                if name.endswith((".py", ".json")):
                    path = os.path.join(dirpath, name)
                    with open(path, "rb") as f:
                        h.update(path.encode() + b"\0" + f.read())
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        h.update(f.read())
    return os.path.join(cache_dir(),
                        f"chipbench-primed-{workload}-{h.hexdigest()[:16]}")


def prime(workload: str, seed: int) -> bool:
    """Build the cell's programs into the persistent cache in a child
    process, unless the stamp says they are there.  Compiling takes device
    memory that a process's peak never gives back, so the measured process
    must only load them.  False if the child failed."""
    if os.path.exists(prime_stamp(workload)):
        return True
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--workload", workload,
         "--seed", str(seed), "--seconds", "0", "--prime"],
        stdout=sys.stderr, timeout=1000)
    return proc.returncode == 0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_files(workload: str) -> dict:
    """The cell's entry and everything it names."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    return {"spec": spec, "cell": cell,
            "cfg": load_json(BENCH, "configs", cell["config"] + ".json"),
            "traffic": load_json(BENCH, "traffic",
                                 cell["traffic"] + ".json"),
            "limits": load_json(BENCH, "limits", workload + ".json")}


def metrics_for(spec: dict, workload: str, traced: bool) -> list:
    """The metric entries this run reports: per-layer ones when traced,
    end-to-end ones otherwise, each only where its ``workloads`` (if
    given) list the cell."""
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in entries
            if workload in m.get("workloads", [workload])]


def load_module(kind: str, path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx: dict):
    return load_module("metric", os.path.join(BENCH, "metrics",
                                              name + ".py")).read(ctx)


def load_model(name: str):
    """The module ``<name>.py`` in :data:`MODELS`: everything of a model
    that the harness does not share.  It provides

    * ``init(words, cfg) -> leaves``: the reference's float32 weights, a
      flat list in a fixed order, from a key's words
      (:func:`chipbench.reference.seed_words`); jitted by the caller;
    * ``to_program(leaves)`` and ``from_program(params)``: the program's
      parameter tree to and from those leaves;
    * ``trainer_kwargs(cfg) -> dict``: what the program's ``Trainer`` is
      told about the model;
    * ``loss_and_grad(leaves, batch, data, precision, keep)``: the plain
      reference's mean loss over the first ``keep`` share of the batch's
      roots and its gradient per leaf, its products computed at
      ``precision`` by :mod:`chipbench.reference`'s shared ones;
    * ``output_leaves(n) -> list[bool]``: which of ``n`` leaves are the
      output layer's, the leaves ``grad_norm_gap`` compares;
    * ``step_work(batch, cfg) -> {"ell_ops", "ell_bytes", "model_ops"}``:
      the operations and bytes one step on ``batch`` requires.

    The loss is a softmax cross-entropy over single labels, since the
    benchmark's data (``chipbench/data.py``) makes one class per node; a
    multilabel model would first need multilabel data there."""
    path = os.path.join(MODELS, name + ".py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(MODELS)
                      if f.endswith(".py"))
        raise SystemExit(f"unknown model {name!r}; models: {have}")
    mod = load_module("model", path)
    missing = [f for f in MODEL_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"model {name!r} lacks {missing}")
    return mod


def finite(x: float) -> float:
    """JSON has no infinity: a non-finite reading prints as the largest
    float, which fails every limit just the same."""
    return x if math.isfinite(x) else sys.float_info.max


def find_chips(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        print(f"run: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run: the cell needs {chips} chips; JAX sees {len(devs)}",
              file=sys.stderr)
        return None
    return devs


def trace_context(path: str) -> dict:
    """The trace reduced to plain tuples, and the window on its clock: from
    the first window step's host span to the last one's end."""
    from chipbench import trace as tracemod

    tr = tracemod.load(path)
    spans = [(a, b) for _, name, a, b in tr["host"] if name == STEP_SPAN]
    if not spans:
        raise RuntimeError(f"no {STEP_SPAN!r} span in {path}")
    return {"trace": tr, "trace_window": (min(a for a, _ in spans),
                                          max(b for _, b in spans))}


def run(argv=None, *, require_chip: bool = True, step_hook=None,
        overrides=None) -> int:
    """One run; ``require_chip``, ``step_hook`` and ``overrides`` (entries
    merged into the cell's files) are for the tests."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--prime", action="store_true",
                    help="set-up only: fill the cache, write the stamp")
    args = ap.parse_args(argv)
    files = cell_files(args.workload)
    for key, val in (overrides or {}).items():
        files[key] = {**files[key], **val}
    cell, cfg, traffic = files["cell"], files["cfg"], files["traffic"]
    if traffic["kind"] != "sampled_train":
        raise SystemExit(f"traffic kind {traffic['kind']!r} has no runner here")
    model = load_model(cfg["model"])
    traced = bool(args.trace)
    if require_chip and not args.prime and not prime(args.workload,
                                                     args.seed):
        print("run: the set-up child that fills the cache failed",
              file=sys.stderr)
        return 1

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # a cache this process opened before (another run in it) keeps its
    # directory until it is reset
    compilation_cache.reset_cache()
    devs = find_chips(cell["chips"], require_chip)
    if devs is None:
        return 1
    peaks = load_json(BENCH, "peaks.json")["devices"]
    kind = devs[0].device_kind
    if traced and kind not in peaks:
        print(f"run: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 1
    from repro.kernels import tune

    from chipbench import compare, reference
    from chipbench.data import make_graph
    from chipbench.train import CompileCounter, TrainCell

    tune.pin(tune.DEFAULTS["tpu"])
    counter = CompileCounter()
    t0 = time.perf_counter()
    data = make_graph(cfg)
    t1 = time.perf_counter()
    tc = TrainCell(cfg, traffic, cell["chips"], data, model,
                   annotate=traced, step_hook=step_hook)
    tc.reseed(args.seed)
    t2 = time.perf_counter()
    prog = tc.warm(traffic["compared_steps"])
    setup_counts = counter.snapshot()
    print(f"run: set-up: jax {t0 - T_START:.2f}s, data {t1 - t0:.2f}s, "
          f"trainer {t2 - t1:.2f}s, warm steps "
          f"{time.perf_counter() - t2:.2f}s (programs {setup_counts[0]} in "
          f"{setup_counts[1]:.2f}s, from the persistent cache "
          f"{setup_counts[2]})", file=sys.stderr)
    if args.prime:
        tc.close()
        os.makedirs(cache_dir(), exist_ok=True)
        with open(prime_stamp(args.workload), "w") as f:
            f.write(f"{setup_counts[0]} programs\n")
        return 0
    if setup_counts[0] > setup_counts[2]:
        print(f"run: set-up compiled {setup_counts[0] - setup_counts[2]} "
              "programs the cache did not hold: peak_hbm_gib includes "
              "compile-time memory", file=sys.stderr)

    # the window compiles as a fresh training job would: nothing from disk
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    win = tc.window(args.seconds)
    if traced:
        jax.profiler.stop_trace()
    window_compiles = counter.snapshot()[0] - setup_counts[0]
    peak = tc.peak_bytes()
    lr, n_chips = tc.lr, len(tc.devices)
    tc.close()
    del tc
    gc.collect()

    # the reference's programs may come from disk: it is not timed
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    t0 = time.perf_counter()
    ref = reference.sgd_steps(model.loss_and_grad, prog["w0"],
                              prog["batches"], data, lr)
    print(f"run: window {len(win['losses'])} steps in {win['seconds']:.3f}s,"
          f" compiles {window_compiles}; reference "
          f"{time.perf_counter() - t0:.2f}s", file=sys.stderr)
    values = compare.numbers(prog, ref, lr,
                             model.output_leaves(len(prog["w0"])))
    failed = sum(not math.isfinite(x) for x in win["losses"])
    values["window_failed_steps"] = failed
    correct, checks = compare.judge(
        values, {**files["limits"], "window_failed_steps": 0})

    ctx = {"cell": cell, "cfg": cfg, "traffic": traffic, "chips": n_chips,
           "window": win, "steps": len(win["losses"]), "setup_s": setup_s,
           "peak_bytes": peak, "window_compiles": window_compiles,
           "setup_programs": setup_counts[0],
           "distinct_batches": traffic["distinct_batches"],
           "peaks": peaks.get(kind),
           "model": model,
           "trace": None}
    device = {"platform": devs[0].platform, "kind": kind, "count": n_chips,
              "memory_peak_bytes": peak}
    result = {}
    if traced:
        from chipbench import trace as tracemod

        ctx.update(trace_context(tracemod.find(TRACE_DIR)))
        lo, hi = ctx["trace_window"]
        ops = list(ctx["trace"]["devices"].values())
        device["busy_s"] = sum(tracemod.busy_ns(o, lo, hi)
                               for o in ops) / max(len(ops), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = tracemod.breakdown(
            ctx["trace"], lo, hi, skip_host="^" + STEP_SPAN)
    metrics = {}
    for m in metrics_for(files["spec"], args.workload, traced):
        value = read_metric(m["name"], ctx)
        if value is None and not traced:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": len(win["losses"]),
           "failed": failed, "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {k: {"value": finite(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
