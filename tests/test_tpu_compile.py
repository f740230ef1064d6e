"""The Pallas aggregation kernels compile for a TPU v5e that is described,
not attached — the tiling and VMEM checks that interpret mode cannot make.

Every compile here is native (no interpret mode), at the paper's feature
widths (500 input features, hidden 256, 7 classes; each padded to the
128-lane tile) and on the bucket shapes of a power-law plan whose hub
buckets reach K ≥ 1024.  The topology is described inside a module fixture
(never at import), which skips the file where libtpu cannot describe one.
"""
import numpy as np
import pytest

WIDTHS = (500, 256, 7)         # feat → hidden → classes (paper §5.1)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu / cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def hub_plan():
    """ELL tables of a Chung-Lu power-law graph: rows and columns drawn
    ∝ (i+1)^-0.8, so both walks hold hub buckets."""
    from repro.graph.coo import from_edges
    from repro.kernels import edgeplan

    rng = np.random.default_rng(0)
    n, e = 8192, 8192 * 16
    p = (np.arange(n) + 1.0) ** -0.8
    p /= p.sum()
    coo = from_edges(rng.permutation(n)[rng.choice(n, e, p=p)],
                     rng.permutation(n)[rng.choice(n, e, p=p)],
                     rng.uniform(0.1, 1.0, e).astype(np.float32), n, n)
    return n, edgeplan.build_plan(coo, caps="pow2").device_tables()


@pytest.fixture
def native(monkeypatch):
    """Route the ops wrappers to the native kernel (interpret=False) — the
    branch they take on a TPU backend."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    return ops


def _sds(one_chip, a, dtype=None):
    import jax
    return jax.ShapeDtypeStruct(np.shape(a), dtype or np.asarray(a).dtype,
                                sharding=one_chip)


def _compile(fn, *args):
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("walk", ["spmm_ell", "spmm_ell_t"])
def test_ell_buckets_compile(one_chip, hub_plan, native, walk, d):
    """Every bucket of the plan, forward (``spmm_ell`` over the dst-major
    tables) or transpose (``spmm_ell_t`` over the column-major ones)."""
    import jax.numpy as jnp

    n, tables = hub_plan
    key = "cols" if walk == "spmm_ell" else "t_cols"
    cols = tables[key]
    vals = tables[key.replace("cols", "vals")]
    assert max(c.shape[1] for c in cols) >= 1024
    kernel = getattr(native, walk)

    def walk_all(cs, vs, x):
        return [kernel(c, v, x) for c, v in zip(cs, vs)]

    _compile(walk_all, [_sds(one_chip, c) for c in cols],
             [_sds(one_chip, v) for v in vals],
             _sds(one_chip, np.empty((n, d)), jnp.float32))


@pytest.mark.parametrize("d", WIDTHS)
def test_ell_aggregate_grad_compiles(one_chip, hub_plan, native, d):
    """The custom_vjp pair end to end: forward walk + transpose walk."""
    import jax
    import jax.numpy as jnp

    n, tables = hub_plan
    tab = jax.tree_util.tree_map(lambda a: _sds(one_chip, a), tables)
    x = _sds(one_chip, np.empty((n, d)), jnp.float32)
    _compile(lambda t, xx: jax.grad(
        lambda v: jnp.sum(native.ell_aggregate(t, v) ** 2))(xx), tab, x)


@pytest.mark.parametrize("d", WIDTHS)
def test_spmm_block_compiles(one_chip, native, d):
    """Block-Message tiles: 4 destination blocks of 256 rows, a 1024-row
    source shard, 4096 edges per tile."""
    import jax.numpy as jnp

    n_blocks, e_blk, dpc, n_src = 4, 4096, 256, 1024
    idx = _sds(one_chip, np.empty((n_blocks, e_blk)), jnp.int32)
    vals = _sds(one_chip, np.empty((n_blocks, e_blk)), jnp.float32)
    x = _sds(one_chip, np.empty((n_src, d)), jnp.float32)
    _compile(lambda r, c, v, xx: native.spmm_block(r, c, v, xx, dpc),
             idx, idx, vals, x)


def test_hypercube_ell_grad_compiles_on_four_chips(topo, native):
    """The distributed ELL aggregate and its mirror backward inside
    ``shard_map`` on a described 2x2 mesh, with the chip's two feature
    waves: kernel outputs must carry their varying-axes type there."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.aggregate import (hypercube_aggregate_ell,
                                             shard_edges_ell)
    from repro.distributed.sharding import leading_axis_spec
    from repro.graph.coo import from_edges

    n_cores, n, d = 4, 4096, 256
    rng = np.random.default_rng(0)
    coo = from_edges(rng.integers(0, n, 16 * n), rng.integers(0, n, 16 * n),
                     rng.uniform(0.1, 1.0, 16 * n).astype(np.float32), n, n)
    tables = shard_edges_ell(coo, n_cores, caps="pow2").tables
    mesh = Mesh(np.array(topo.devices[:n_cores]), ("model",),
                axis_types=(AxisType.Auto,))
    specs = jax.tree_util.tree_map(leading_axis_spec, tables)
    tab = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tables, specs)
    x = jax.ShapeDtypeStruct((n, d), jnp.float32,
                             sharding=NamedSharding(mesh, P("model", None)))

    def body(t, xl):
        t = jax.tree_util.tree_map(lambda a: a[0], t)
        return hypercube_aggregate_ell("model", 2, n, t, xl, n_chunks=2)

    agg = jax.shard_map(body, mesh=mesh, in_specs=(specs, P("model", None)),
                        out_specs=P("model", None))
    compiled = _compile(
        lambda t, xx: jax.grad(lambda v: jnp.sum(agg(t, v) ** 2))(xx),
        tab, x)
    assert "collective-permute" in compiled.as_text()
