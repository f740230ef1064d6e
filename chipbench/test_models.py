"""A configuration's ``model`` names the module that holds everything
model-specific (``chipbench/models/<model>.py``, loaded by
:func:`chipbench.run.load_model`): each module keeps the contract, an
unknown name fails, the GCN's reference reads as it did before it moved
there, and a model added as a file alone is the one ``correct`` compares
with."""
import json
import os
import shutil

import numpy as np
import pytest

from chipbench import reference, run
from chipbench.test_correct import SMALL
from chipbench.test_work import TOY

MODELS = sorted(f[:-3] for f in os.listdir(run.MODELS) if f.endswith(".py"))


@pytest.mark.parametrize("name", MODELS)
def test_model_keeps_the_contract(name):
    mod = run.load_model(name)
    for f in run.MODEL_FUNCTIONS:
        assert callable(getattr(mod, f, None)), f


def test_unknown_model_fails_and_lists_the_models():
    with pytest.raises(SystemExit, match=r"unknown model 'nope'.*'gcn'"):
        run.run(["--workload", "gcn-reddit.train", "--seed", "1",
                 "--seconds", "0"], require_chip=False,
                overrides={"cfg": {"model": "nope"}})


def test_model_without_a_function_fails(tmp_path, monkeypatch):
    (tmp_path / "bare.py").write_text("def init(words, cfg):\n    return []\n")
    monkeypatch.setattr(run, "MODELS", str(tmp_path))
    with pytest.raises(SystemExit, match="lacks.*'to_program'"):
        run.load_model("bare")


#: ``test_work.TOY``'s widths: 5 features, hidden 4, 3 classes
TOY_CFG = {"feat_dim": 5, "hidden": 4, "n_classes": 3, "n_layers": 2}
#: the float32 readings of ``reference.init_params`` and
#: ``reference.sgd_steps`` from the seed 4000000011 over three steps on TOY
#: with lr 0.05, recorded on a CPU with the code as it stood before the
#: GCN moved into ``chipbench/models/gcn.py``: the initial weights' norms,
#: and per (precision, keep) each step's loss, each leaf's norm after each
#: step and the first gradient's norm per leaf
W0_NORMS = [1.9326850175857544, 1.6941068172454834]
GOLDEN = {
    ("highest", 1.0): {
        "losses": [1.175605297088623, 1.1657581329345703, 1.156233549118042],
        "norms": [[1.930681824684143, 1.6917173862457275],
                  [1.928951382637024, 1.6896406412124634],
                  [1.9274834394454956, 1.6878650188446045]],
        "grad1": [0.41176503896713257, 0.1705404669046402]},
    ("high", 1.0): {
        "losses": [1.1756043434143066, 1.1657580137252808, 1.156233787536621],
        "norms": [[1.930681824684143, 1.6917173862457275],
                  [1.928951382637024, 1.6896406412124634],
                  [1.9274834394454956, 1.6878650188446045]],
        "grad1": [0.41176292300224304, 0.17054063081741333]},
    ("bf16", 1.0): {
        "losses": [1.1751738786697388, 1.165759801864624, 1.1564619541168213],
        "norms": [[1.930684208869934, 1.6917363405227661],
                  [1.9289497137069702, 1.6896536350250244],
                  [1.9274770021438599, 1.6878743171691895]],
        "grad1": [0.4121188223361969, 0.170465886592865]},
    ("highest", 0.5): {
        "losses": [1.163366436958313, 1.152982473373413, 1.1433119773864746],
        "norms": [[1.9310355186462402, 1.6921024322509766],
                  [1.9296709299087524, 1.690425992012024],
                  [1.9285699129104614, 1.6890532970428467]],
        "grad1": [0.4342951774597168, 0.15127384662628174]},
}


def _norms(leaves):
    return [float(np.linalg.norm(np.asarray(w))) for w in leaves]


@pytest.mark.parametrize("precision,keep", list(GOLDEN))
def test_gcn_reads_as_before_it_moved(precision, keep):
    gcn = run.load_model("gcn")
    rng = np.random.default_rng(0)
    data = {"features": rng.standard_normal((4, 5)).astype(np.float32),
            "labels": np.array([2, 0, 1, 1], np.int32)}
    ws0 = gcn.init(reference.seed_words(4000000011), TOY_CFG)
    np.testing.assert_allclose(_norms(ws0), W0_NORMS, rtol=1e-6)
    out = reference.sgd_steps(gcn.loss_and_grad, ws0, [TOY] * 3, data, 0.05,
                              precision=precision, keep=keep)
    want = GOLDEN[precision, keep]
    np.testing.assert_allclose(out["losses"], want["losses"], rtol=1e-6)
    np.testing.assert_allclose([_norms(p) for p in out["params"]],
                               want["norms"], rtol=1e-6)
    np.testing.assert_allclose(_norms(out["grads1"]), want["grad1"],
                               rtol=1e-6)


RELU = "h = jnp.maximum(h, 0.0)"


@pytest.mark.parametrize("name,edit,correct", [
    ("gcn_copy", None, True),
    ("gcn_no_relu", (RELU, "pass"), False),
])
def test_model_added_as_a_file(tmp_path, monkeypatch, capsys, name, edit,
                               correct):
    """A model in a directory of its own, named by the configuration: the
    faithful copy of the GCN reads correct, and a copy whose reference
    drops the ReLU does not, so the comparison is with the named model's
    own reference."""
    src = open(os.path.join(run.MODELS, "gcn.py")).read()
    if edit:
        assert src.count(edit[0]) == 1
        src = src.replace(*edit)
    (tmp_path / f"{name}.py").write_text(src)
    monkeypatch.setattr(run, "MODELS", str(tmp_path))
    rc = run.run(["--workload", "gcn-reddit.train", "--seed", "4000000011",
                  "--seconds", "0.5", "--trace", "0"], require_chip=False,
                 overrides={**SMALL,
                            "cfg": {**SMALL["cfg"], "model": name}})
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is correct, out["checks"]


def test_prime_stamp_covers_the_models(tmp_path, monkeypatch):
    bench = tmp_path / "chipbench"
    shutil.copytree(run.MODELS, bench / "models")
    monkeypatch.setattr(run, "BENCH", str(bench))
    before = run.prime_stamp("gcn-reddit.train")
    with open(bench / "models" / "gcn.py", "a") as f:
        f.write("\n")
    assert run.prime_stamp("gcn-reddit.train") != before
