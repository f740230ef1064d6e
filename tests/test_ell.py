"""Pre-reduced ELL aggregation engine: plan builder, kernels, custom_vjp,
distributed aggregate, autotuner.

Contracts:
  * the degree-bucketed ELL tables reproduce the COO oracle exactly
    (forward AND the column-major transpose walk), on both the pure-XLA
    path and the Pallas kernel (interpret mode off-TPU);
  * ELL padding is routed to a dedicated zero row / out-of-range fill —
    never to real row 0 — and empty destination blocks produce exact zeros;
  * the ELL engine layer (``Engine("ell+pipelined").layer``) matches the
    serial ``gcn_layer`` forward and grads;
  * EdgePlans are built once per graph and cached on the COO identity;
  * the distributed ELL aggregate matches the serial hypercube aggregate
    to ≤1e-5 abs (fp32) on 2/4/8 simulated devices, and the overlapped ELL
    train step tracks the serial loss trajectory;
  * the autotuner persists a JSON winner that ``get_config`` then serves.
"""
import textwrap

import numpy as np
import pytest

from conftest import run_subprocess


def _skewed_coo(rng, n_dst, n_src, e, hub_extra=60):
    """Random graph with a hub row (degree skew) and isolated dst rows."""
    from repro.graph.coo import from_edges

    rows = np.concatenate([rng.integers(0, n_dst, e),
                           np.full(hub_extra, min(3, n_dst - 1))])
    cols = rng.integers(0, n_src, len(rows))
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    iso = rng.integers(0, n_dst, max(n_dst // 8, 1))   # isolated dst rows
    keep = ~np.isin(rows, iso)
    return from_edges(rows[keep], cols[keep], vals[keep], n_dst, n_src)


# ---------------------------------------------------------------------------
# Plan builder + kernels vs the COO oracles.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_dst,n_src,d,e", [
    (64, 64, 32, 500),
    (70, 53, 19, 600),          # non-multiple-of-tile everything
    (8, 200, 33, 777),
    (130, 96, 64, 1),           # near-empty graph
])
@pytest.mark.parametrize("caps", ["pow2", "single", (2, 8)])
def test_ell_walk_matches_oracle(rng, n_dst, n_src, d, e, caps):
    import jax.numpy as jnp
    from repro.kernels import edgeplan
    from repro.kernels.ops import ell_apply
    from repro.kernels.ref import spmm_ref, spmm_t_ref

    coo = _skewed_coo(rng, n_dst, n_src, e)
    plan = edgeplan.build_plan(coo, caps=caps)
    tables = plan.device_tables()
    x = jnp.asarray(rng.standard_normal((n_src, d)), jnp.float32)
    ref = np.asarray(spmm_ref(coo.rows, coo.cols, coo.vals, x, n_dst))
    for use_pallas in (False, True):
        out = np.asarray(ell_apply(tables, x, use_pallas=use_pallas))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    err = jnp.asarray(rng.standard_normal((n_dst, d)), jnp.float32)
    tref = np.asarray(spmm_t_ref(coo.rows, coo.cols, coo.vals, err, n_src))
    for use_pallas in (False, True):
        out = np.asarray(ell_apply(tables, err, transpose=True,
                                   use_pallas=use_pallas))
        np.testing.assert_allclose(out, tref, rtol=1e-5, atol=1e-5)


def test_spmm_ell_kernel_direct(rng):
    """The raw bucketed kernel (one bucket at a time) vs a dense gather."""
    import jax.numpy as jnp
    from repro.kernels.ops import spmm_ell, spmm_ell_t

    nb, K, n_src, d = 37, 5, 41, 23
    cols = rng.integers(0, n_src + 1, (nb, K)).astype(np.int32)
    vals = rng.standard_normal((nb, K)).astype(np.float32)
    vals[cols == n_src] = 0.0           # padding entries -> zero row, val 0
    x = rng.standard_normal((n_src, d)).astype(np.float32)
    xz = np.concatenate([x, np.zeros((1, d), np.float32)])
    ref = (xz[cols] * vals[..., None]).sum(axis=1)
    out = np.asarray(spmm_ell(jnp.asarray(cols), jnp.asarray(vals),
                              jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # spmm_ell_t is the same kernel by contract
    out_t = np.asarray(spmm_ell_t(jnp.asarray(cols), jnp.asarray(vals),
                                  jnp.asarray(x)))
    np.testing.assert_allclose(out_t, ref, rtol=1e-5, atol=1e-5)


def test_spmm_ell_hub_bucket_degree_slices(rng):
    """A bucket wider than the kernel's degree tile (K=300 → padded to 384,
    three grid slices summed into one merge matrix) vs a dense gather."""
    import jax.numpy as jnp
    from repro.kernels.ops import spmm_ell
    from repro.kernels.spmm import ELL_BK

    nb, K, n_src, d = 19, 300, 150, 9
    assert K > ELL_BK
    cols = rng.integers(0, n_src + 1, (nb, K)).astype(np.int32)
    vals = rng.standard_normal((nb, K)).astype(np.float32)
    vals[cols == n_src] = 0.0
    x = rng.standard_normal((n_src, d)).astype(np.float32)
    xz = np.concatenate([x, np.zeros((1, d), np.float32)])
    ref = (xz[cols] * vals[..., None]).sum(axis=1)
    out = np.asarray(spmm_ell(jnp.asarray(cols), jnp.asarray(vals),
                              jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_ell_padding_never_touches_real_rows(rng):
    """Poisoned row 0: padding must gather the dedicated zero row, not real
    data — even when every padding val is (wrongly) nonzero."""
    import jax.numpy as jnp
    from repro.kernels.ops import spmm_ell

    nb, K, n_src, d = 8, 3, 16, 7
    cols = np.full((nb, K), n_src, np.int32)      # ALL entries -> zero row
    vals = np.ones((nb, K), np.float32)           # poisoned weights
    x = np.full((n_src, d), 1e9, np.float32)      # poisoned real rows
    out = np.asarray(spmm_ell(jnp.asarray(cols), jnp.asarray(vals),
                              jnp.asarray(x)))
    assert np.all(out == 0.0), "padding gathered real data"


def test_empty_destination_block_is_noop(rng):
    """A destination block with zero edges costs nothing and outputs exact
    zeros (inv_perm routes its rows to the zero output row)."""
    import jax.numpy as jnp
    from repro.graph.coo import from_edges
    from repro.kernels import edgeplan
    from repro.kernels.ops import ell_apply

    n_dst, n_src, d = 64, 64, 16            # 4 blocks of 16 dst rows
    rows = rng.integers(0, 16, 300)         # ALL edges land in block 0
    coo = from_edges(rows, rng.integers(0, n_src, 300),
                     rng.standard_normal(300).astype(np.float32),
                     n_dst, n_src)
    plan = edgeplan.build_plan(coo)
    x = jnp.asarray(np.full((n_src, d), 7.0, np.float32))
    for use_pallas in (False, True):
        out = np.asarray(ell_apply(plan.device_tables(), x,
                                   use_pallas=use_pallas))
        assert np.all(out[16:] == 0.0), "empty blocks must be exact zeros"
        assert np.any(out[:16] != 0.0)


def test_coo_out_of_range_padding_cols_are_noops(rng):
    """The wrappers now route padding cols PAST the source range, so the
    gather one-hot matches nothing: an out-of-range col is a no-op even
    with a NONZERO weight (the old col-0 padding relied entirely on
    val == 0 zeroing a gather of real row 0 after the fact)."""
    import jax.numpy as jnp
    from repro.kernels.ref import spmm_ref
    from repro.kernels.spmm import spmm as spmm_raw

    n_dst, n_src, d, e = 32, 48, 128, 256
    rows = rng.integers(0, n_dst, e).astype(np.int32)
    cols = rng.integers(0, n_src, e).astype(np.int32)
    vals = rng.standard_normal(e).astype(np.float32)
    cols[200:] = n_src                        # out-of-range "padding"
    vals[200:] = 7.0                          # ...with poisoned weights
    x = jnp.asarray(rng.standard_normal((n_src, d)), jnp.float32)
    out = np.asarray(spmm_raw(jnp.asarray(rows), jnp.asarray(cols),
                              jnp.asarray(vals), x, n_dst, interpret=True))
    ref = np.asarray(spmm_ref(rows[:200], cols[:200], vals[:200], x, n_dst))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Layer-level: the ELL engine layer vs the serial transpose-free layer.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("order", ["coag", "agco"])
@pytest.mark.parametrize("activate", [True, False])
def test_ell_engine_layer_matches_reference(rng, order, activate):
    import jax
    import jax.numpy as jnp
    from repro.core.gcn import gcn_layer
    from repro.engine import Engine

    n_dst, n_src, d, h, e = 64, 96, 24, 12, 700
    coo = _skewed_coo(rng, n_dst, n_src, e)
    eng = Engine("ell+pipelined")
    x = jnp.asarray(rng.standard_normal((n_src, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, h)), jnp.float32)
    y_ref = gcn_layer(coo, x, w, order=order, activate=activate)
    y_ell = eng.layer(coo, x, w, order=order, activate=activate)
    np.testing.assert_allclose(np.asarray(y_ell), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) ** 2)

    g_ref = jax.grad(loss(lambda x, w: gcn_layer(
        coo, x, w, order=order, activate=activate)), argnums=(0, 1))(x, w)
    g_ell = jax.grad(loss(lambda x, w: eng.layer(
        coo, x, w, order=order, activate=activate)), argnums=(0, 1))(x, w)
    for a, b in zip(g_ref, g_ell):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-3, atol=2e-3)


def test_message_rowlists_is_the_merge_plan(rng):
    """Walking a Block Message with message_rowlists reconstructs exactly
    the per-slot neighbor groups the ELL rows store: one yield per wire
    message, whose (B, D, w) slices rebuild the block's edge set and whose
    lengths are the pre-merge fan-ins."""
    from repro.core.blockmsg import compress_block, message_rowlists

    lr = rng.integers(0, 16, 120)
    lc = rng.integers(0, 16, 120)
    v = rng.standard_normal(120).astype(np.float32)
    bm = compress_block(lr, lc, v, dst_core=2, src_core=5)
    seen = []
    for b, d_slots, w in message_rowlists(bm):
        assert len(d_slots) == len(w) > 0
        seen.extend((b, int(d), float(x)) for d, x in zip(d_slots, w))
    assert sorted(seen) == sorted(
        (int(r), int(c), float(x)) for r, c, x in zip(lr, lc, v))
    assert [b for b, _, _ in message_rowlists(bm)] \
        == sorted(set(int(r) for r in lr))


# ---------------------------------------------------------------------------
# Plan cache: built once per graph, keyed on the COO identity.
# ---------------------------------------------------------------------------
def test_edgeplan_cache_hit(rng):
    from repro.graph.coo import from_edges
    from repro.kernels import edgeplan

    coo = from_edges(rng.integers(0, 32, 100), rng.integers(0, 32, 100),
                     rng.standard_normal(100).astype(np.float32), 32, 32)
    p1 = edgeplan.build_plan(coo, caps="pow2")
    p2 = edgeplan.build_plan(coo, caps="pow2")
    assert p1 is p2, "second build must return the cached object"
    # different caps -> different plan; same arrays -> still cached per key
    p3 = edgeplan.build_plan(coo, caps="single")
    assert p3 is not p1
    assert edgeplan.build_plan(coo, caps="single") is p3
    # a different COO (fresh arrays) must NOT hit the cache
    coo2 = from_edges(np.asarray(coo.rows).copy(),
                      np.asarray(coo.cols).copy(),
                      np.asarray(coo.vals).copy(), 32, 32)
    assert edgeplan.build_plan(coo2, caps="pow2") is not p1


def test_shard_edges_ell_cache_hit(rng):
    from repro.distributed.aggregate import shard_edges_ell
    from repro.graph.coo import from_edges

    coo = from_edges(rng.integers(0, 32, 200), rng.integers(0, 32, 200),
                     rng.standard_normal(200).astype(np.float32), 32, 32)
    assert shard_edges_ell(coo, 4) is shard_edges_ell(coo, 4)
    assert shard_edges_ell(coo, 4) is not shard_edges_ell(coo, 2)


# ---------------------------------------------------------------------------
# Autotuner: sweep -> JSON -> get_config.
# ---------------------------------------------------------------------------
def test_autotune_persists_and_serves(tmp_path, monkeypatch):
    from repro.kernels import tune

    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(tune.ENV_PATH, path)
    tune.reset()
    rec = tune.autotune(n=64, deg=3, d=8, n_reps=1)
    assert rec["backend"] and "caps" in rec["config"]
    cfg = tune.get_config()
    assert cfg["caps"] == rec["config"]["caps"]
    # idempotent: second call reads the file, no re-sweep
    rec2 = tune.autotune(n=64, deg=3, d=8, n_reps=1)
    assert rec2["config"] == rec["config"]
    tune.reset()


# ---------------------------------------------------------------------------
# Distributed: ≤1e-5 vs the serial path on 2/4/8 simulated devices.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_distributed_ell_matches_serial(n_devices):
    ndim = int(np.log2(n_devices))
    run_subprocess(textwrap.dedent(f"""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.graph.coo import from_edges
        from repro.distributed.aggregate import (
            shard_edges, shard_edges_ell, hypercube_aggregate,
            hypercube_aggregate_ell)

        PC, ndim = {n_devices}, {ndim}
        n_dst, n_src, d, e = 16 * PC, 32 * PC, 20, 2500
        rng = np.random.default_rng(0)
        coo = from_edges(rng.integers(0, n_dst, e),
                         rng.integers(0, n_src, e),
                         rng.standard_normal(e).astype(np.float32),
                         n_dst, n_src)
        x = jnp.asarray(rng.standard_normal((n_src, d)), jnp.float32)
        mesh = Mesh(np.array(jax.devices()), ('model',))
        es = shard_edges(coo, PC)
        ee = shard_edges_ell(coo, PC)
        ser = shard_map(
            lambda r, c, v, xl: hypercube_aggregate(
                'model', ndim, n_dst, r[0], c[0], v[0], xl),
            mesh=mesh, in_specs=(P('model'),) * 4, out_specs=P('model'))
        ys = np.asarray(ser(jnp.asarray(es.rows_global),
                            jnp.asarray(es.cols_local),
                            jnp.asarray(es.vals), x))
        tabs = jax.tree_util.tree_map(jnp.asarray, ee.tables)
        especs = jax.tree_util.tree_map(
            lambda a: P('model', *([None] * (a.ndim - 1))), tabs)
        for nc in (1, 2):
            agg = shard_map(
                lambda t, xl, nc=nc: hypercube_aggregate_ell(
                    'model', ndim, n_dst,
                    jax.tree_util.tree_map(lambda a: a[0], t), xl, nc),
                mesh=mesh, in_specs=(especs, P('model')),
                out_specs=P('model'))
            ye = np.asarray(agg(tabs, x))
            assert np.abs(ys - ye).max() <= 1e-5, (nc, np.abs(ys - ye).max())
            g1 = jax.grad(lambda xx: jnp.sum(agg(tabs, xx) ** 2))(x)
            g2 = jax.grad(lambda xx: jnp.sum(coo.matmul(xx) ** 2))(x)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=2e-3, atol=2e-3)
        print('OK')
    """), n_devices=n_devices)


def test_ell_mesh_mismatch_fails_loudly():
    """A batch built for 8 cores on a 4-core mesh must raise, not silently
    drop half the senders' tables (the blocked path's tile-count guard,
    re-established for the ELL layout)."""
    run_subprocess(textwrap.dedent("""
        import jax, numpy as np
        from repro.distributed.gcn_train import init_params
        from repro.engine import Engine
        from repro.graph.coo import from_edges

        rng = np.random.default_rng(0)

        class _MB:
            layers = [from_edges(rng.integers(0, 32, 200),
                                 rng.integers(0, 64, 200),
                                 rng.standard_normal(200).astype(np.float32),
                                 32, 64)]

        feats = rng.standard_normal((64, 8)).astype(np.float32)
        labels = rng.integers(0, 4, 32).astype(np.int32)
        eng = Engine('ell+pipelined')
        batch = eng.build(n_cores=8).shard_batch(_MB(), feats, labels)
        mesh = jax.make_mesh((4,), ('model',))
        step = eng.build(mesh).train_step_fn(batch['dims'])
        params = init_params(jax.random.PRNGKey(0), [(8, 4)])
        try:
            step(params, batch)
        except ValueError as e:
            assert 'different core count' in str(e), e
            print('OK raised')
        else:
            raise AssertionError('mesh/layout mismatch not detected')
    """), n_devices=4)


def test_ell_train_step_matches_serial():
    """The ell+pipelined engine tracks the coo+serial loss trajectory
    (≤1e-5; the merge reorders fp32 adds)."""
    run_subprocess(textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.graph import NeighborSampler, make_dataset
        from repro.distributed.gcn_train import init_params
        from repro.engine import Engine, EngineConfig

        ds = make_dataset('flickr', scale=0.005, feat_dim=32)
        sampler = NeighborSampler(ds.graph, fanouts=(5, 5),
                                  pad_multiple=8, seed=0)
        rng = np.random.default_rng(0)
        seeds = rng.permutation(ds.graph.n_nodes)[:32]
        mb = sampler.sample(seeds, rng=np.random.default_rng(1))
        feats = ds.features[np.minimum(mb.input_nodes,
                                       ds.graph.n_nodes - 1)]
        pad = mb.layers[0].n_dst - len(seeds)
        labels = ds.labels[np.pad(seeds, (0, pad))] % 7

        mesh = jax.make_mesh((8,), ('model',))
        params = init_params(jax.random.PRNGKey(0), [(32, 16), (16, 7)])
        ser = Engine(EngineConfig.from_spec('coo+serial',
                                            lr=0.3)).build(mesh)
        ell = Engine(EngineConfig.from_spec('ell+pipelined', lr=0.3,
                                            n_chunks=2)).build(mesh)
        b_ser = ser.shard_batch(mb, feats, labels)
        b_ell = ell.shard_batch(mb, feats, labels)
        p1, p2 = params, params
        for i in range(5):
            p1, l1 = ser.train_step(p1, b_ser)
            p2, l2 = ell.train_step(p2, b_ell)
            assert abs(float(l1) - float(l2)) < 1e-5, (i, float(l1),
                                                       float(l2))
        print('OK', float(l1))
    """), n_devices=8)
