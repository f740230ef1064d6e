#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers over many
seeds, and the numbers of the controls and planted faults on the same
batches.  Run on the chip, at the cell's own size, in one process:

    python3 chipbench/readings.py --workload gcn-reddit.train \\
        --seeds 101,102,103 --controls high,bf16 --keep 0.5

For each seed the program runs its set-up steps (the window's own call and
feed) and is compared with the plain reference as a benchmark run compares
it; each control is the reference at a lower precision put in the
program's place, and ``--keep`` the reference with that share of the batch
kept.  ``--drop-exchange`` plants the dropped exchange in the program
itself.  One JSON line per seed and kind; nothing here is timed.
"""
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--keep", type=float, default=None)
    ap.add_argument("--drop-exchange", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from chipbench import compare, faults, reference, run
    from chipbench.data import make_graph
    from chipbench.train import TrainCell

    jax.config.update("jax_compilation_cache_dir", run.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    files = run.cell_files(args.workload)
    cell, cfg, traffic = files["cell"], files["cfg"], files["traffic"]
    if run.find_chips(cell["chips"], require_chip=True) is None:
        return 1
    from repro.kernels import tune

    tune.pin(tune.DEFAULTS["tpu"])
    data = make_graph(cfg)
    compared = traffic["compared_steps"]
    with (faults.drop_exchange() if args.drop_exchange
          else contextlib.nullcontext()):
        tc = TrainCell(cfg, traffic, cell["chips"], data)
        for seed in [int(s) for s in args.seeds.split(",")]:
            tc.reseed(seed)
            prog = tc.warm(compared)
            ref = reference.sgd_steps(prog["w0"], prog["batches"], data,
                                      tc.lr)
            kind = "drop_exchange" if args.drop_exchange else "program"
            _emit(args.workload, seed, kind,
                  compare.numbers(prog, ref, tc.lr))
            others = [(c, {"precision": c})
                      for c in args.controls.split(",") if c]
            if args.keep is not None:
                others.append((f"keep_{args.keep:g}", {"keep": args.keep}))
            for name, kw in others:
                alt = reference.sgd_steps(prog["w0"], prog["batches"], data,
                                          tc.lr, **kw)
                as_prog = {"losses": alt["losses"], "w0": prog["w0"],
                           "w1": alt["params"][0],
                           "w_last": alt["params"][-1]}
                _emit(args.workload, seed, name,
                      compare.numbers(as_prog, ref, tc.lr))
        tc.close()
    return 0


def _emit(workload, seed, kind, values):
    print(json.dumps({"workload": workload, "seed": seed, "kind": kind,
                      **values, "at_s": round(time.perf_counter(), 1)}),
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
