"""The graph generator's CSR holds every drawn pair in both directions."""
import numpy as np

from chipbench.data import make_graph

SMALL = {"n_nodes": 500, "n_edges": 4000, "alpha": 1.8, "feat_dim": 6,
         "n_classes": 3, "data_seed": 5}


def test_csr_is_the_symmetrised_pairs():
    g = make_graph(SMALL)
    n = SMALL["n_nodes"]
    deg = np.diff(g["indptr"])
    rows = np.repeat(np.arange(n), deg)
    cols = g["indices"]
    assert not np.any(rows == cols)
    # every entry's reverse is an entry, as often
    fwd = np.sort(rows * n + cols)
    rev = np.sort(cols * n + rows)
    np.testing.assert_array_equal(fwd, rev)
    # about n_edges pairs survive the self-edge drop, each stored twice
    assert 2 * 3600 < cols.size <= 2 * SMALL["n_edges"]
    assert g["features"].shape == (n, 6) and g["features"].dtype == np.float32
    assert abs(float(g["features"].std()) - 0.1) < 0.01
    assert g["labels"].min() >= 0 and g["labels"].max() < 3


def test_same_seed_same_graph():
    a, b = make_graph(SMALL), make_graph(SMALL)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
