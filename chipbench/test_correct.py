"""``correct`` comes out true for a sound run and false for each fault a
training cell can have, and for the control: whole runs of the harness,
past its look for a chip, on the CPU, held to the cells' own limits.

The graph and the batch are cut to a test's size; the widths are the
cells' own.  The control here is the reference at one bf16 pass.  The
configuration's own control, three passes (``high``), fails no number of
either cell on the chip at the cells' sizes (``PERF.md``), so it is not
tested here."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import faults, run

#: the cells' files with the graph and the batch cut to a CPU test's size
SMALL = {"cfg": {"n_nodes": 3000, "n_edges": 15000},
         "traffic": {"batch_size": 64, "pad_multiple": 64,
                     "fanouts": [3, 4]}}


def result(capsys, workload="gcn-reddit.train", step_hook=None) -> dict:
    rc = run.run(["--workload", workload, "--seed", "4000000011",
                  "--seconds", "0.5", "--trace", "0"], require_chip=False,
                 step_hook=step_hook, overrides=SMALL)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = result(capsys)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("hook", [
    faults.state_unchanged,
    faults.reference(keep=0.5),
    faults.reference(precision="bf16"),
], ids=["state_unchanged", "half_batch", "control_bf16"])
def test_fault_reads_incorrect(capsys, hook):
    out = result(capsys, step_hook=hook)
    assert not out["correct"], out["checks"]


DROPPED = """
import json, sys
sys.path[0:0] = [{root!r}, {src!r}]
from chipbench import faults, run
from chipbench.test_correct import SMALL
args = ["--workload", "gcn-flickr.train-x4", "--seed", "17",
        "--seconds", "0.5", "--trace", "0"]
run.run(args, require_chip=False, overrides=SMALL)
with faults.drop_exchange():
    run.run(args, require_chip=False, overrides=SMALL)
"""


def test_dropped_exchange_reads_incorrect():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DROPPED.format(root=run.ROOT, src=os.path.join(run.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(s) for s in proc.stdout.splitlines()
             if s.startswith("{")]
    assert [r["device"]["count"] for r in lines] == [4, 4]
    sound, dropped = lines
    assert sound["correct"], sound["checks"]
    assert not dropped["correct"], dropped["checks"]


def test_prime_fills_the_cache_and_stamps_it(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    stamp = run.prime_stamp("gcn-reddit.train")
    assert os.path.dirname(stamp) == str(tmp_path)
    assert not os.path.exists(stamp)
    rc = run.run(["--workload", "gcn-reddit.train", "--seed", "4000000011",
                  "--seconds", "0", "--prime"], require_chip=False,
                 overrides=SMALL)
    assert rc == 0 and os.path.exists(stamp)
    assert capsys.readouterr().out == ""
    assert len(os.listdir(tmp_path)) > 1
    assert run.prime("gcn-reddit.train", 4000000011)
