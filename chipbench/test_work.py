"""The op and byte counts of ``chipbench/work.py`` against hand counts."""
import numpy as np

from chipbench import work

# two hops, shallowest first; hop 0 draws (1, 2) twice
TOY = {"n_roots": 2, "nodes": np.arange(4), "hops": [
    {"rows": np.array([0, 0, 1, 1, 1]), "cols": np.array([0, 1, 1, 2, 2]),
     "n_dst": 2, "n_src": 3},
    {"rows": np.array([0, 1, 2, 2]), "cols": np.array([0, 1, 2, 3]),
     "n_dst": 3, "n_src": 4}]}
# layer 0 (5 -> 4) feeds the deepest hop, layer 1 (4 -> 3) hop 0
DIMS = [(5, 4), (4, 3)]


def test_distinct_pairs():
    assert work.hop_nnz(TOY["hops"][0]) == 4
    assert work.hop_nnz(TOY["hops"][1]) == 4


def test_step_work_by_hand():
    # hop 0, width 3: a walk is 2*4*3 = 24 ops and (3 + 2)*3*4 + 4*8 = 92
    # bytes; hop 1, width 4: 2*4*4 = 32 ops, (4 + 3)*4*4 + 4*8 = 144 bytes;
    # two walks a hop
    assert work.step_work(TOY, DIMS) == {
        "ell_ops": 2 * 24 + 2 * 32,
        "ell_bytes": 2 * 92 + 2 * 144,
        # hop 0: combination 2*3*4*3 = 72, its weight and input gradients
        # 72 each, aggregation 24 forward + 24 backward; hop 1 (the raw
        # features, no input gradient): 2*4*5*4 = 160 twice, 32 + 32
        "model_ops": (72 * 3 + 48) + (160 * 2 + 64)}


def test_window_work_sums_repeats():
    one = work.step_work(TOY, DIMS)
    assert work.window_work([TOY, TOY, TOY], DIMS) == {
        k: 3 * v for k, v in one.items()}
