"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's math on the CPU but skips Mosaic, the
compiler that refuses misaligned slices, over-budget VMEM and loops it
cannot legalize. These tests hand each kernel to the real TPU compiler
for a ``v5e:2x2`` topology that is described, not attached
(``jax.experimental.topologies``), at the widths ``chip_smoke.py`` runs:
nothing executes, so they guard compile-ability at no chip time.

The topology is described inside the module fixture (never at import):
only one process may load the TPU library, and xdist workers import
every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

N, D, K = 1_000_000, 128, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    """AOT-compile ``fn`` for the described chip; ``shapes`` are
    ``(shape, dtype)`` pairs. Returns the compiled executable's HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_knn(one_chip, dtype):
    from raft_tpu.ops.fused_knn import fused_knn

    _compile(one_chip,
             functools.partial(fused_knn, k=K, interpret=False),
             ((1000, D), jnp.float32), ((500_000, D), dtype))


def test_fused_knn_graph_k(one_chip):
    # k=129: CAGRA's intermediate-graph sweep (kp=256 halves the query tile)
    from raft_tpu.ops.fused_knn import fused_knn

    _compile(one_chip,
             functools.partial(fused_knn, k=129, interpret=False),
             ((1000, D), jnp.float32), ((250_000, D), jnp.float32))


@pytest.mark.parametrize("k", [10, 64])
def test_select_k_kpass(one_chip, k):
    from raft_tpu.matrix.select_k import _kpass_2d

    _compile(one_chip, lambda v: _kpass_2d(v, k, False),
             ((10_000, 4096), jnp.float32))


_L, _P, _LMAX, _M = 1024, 20, 2048, 1000


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint8],
                         ids=["f32", "bf16", "u8"])
def test_ivf_flat_scan(one_chip, dtype):
    # every storage dtype the scan streams from HBM
    from raft_tpu.ops.ivf_scan import ivf_flat_scan

    fn = functools.partial(ivf_flat_scan, k=K, lmax=_LMAX, interpret=False)
    _compile(one_chip, fn, ((N, D), dtype), ((N,), jnp.float32),
             ((_M, _P), jnp.int32), ((_L,), jnp.int32), ((_L,), jnp.int32),
             ((_M, D), jnp.float32))


def _compile_ivf_pq_scan(sharding, lut, pq_bits, m):
    from raft_tpu.ops.ivf_pq_scan import ivf_pq_scan, make_cb_matrix

    pq_dim, book = 64, 1 << pq_bits

    def fn(codes, norms, centers, cbs, probed, offs, sizes, q):
        return ivf_pq_scan(codes, norms, centers, make_cb_matrix(cbs), probed,
                           offs, sizes, q, k=K * 2, lmax=_LMAX, pq_dim=pq_dim,
                           book=book, lut_mode=lut, interpret=False)

    _compile(sharding, fn, ((N, pq_dim), jnp.uint8), ((N,), jnp.float32),
             ((_L, D), jnp.float32), ((pq_dim, book, D // pq_dim), jnp.float32),
             ((m, _P), jnp.int32), ((_L,), jnp.int32), ((_L,), jnp.int32),
             ((m, D), jnp.float32))


@pytest.mark.parametrize("lut", ["f32", "bf16", "int8"])
def test_ivf_pq_scan(one_chip, lut):
    _compile_ivf_pq_scan(one_chip, lut, 8, _M)


# the served bucket's 256-query grid, and the one-hot's (pq_dim, book)
# row merge at a 16-entry book (the kernel body does not depend on m)
@pytest.mark.parametrize("pq_bits,m", [(8, 256), (4, 256)])
@pytest.mark.parametrize("lut", ["f32", "bf16", "int8"])
def test_ivf_pq_scan_bits_batch(one_chip, lut, pq_bits, m):
    _compile_ivf_pq_scan(one_chip, lut, pq_bits, m)


_DEG, _ITOPK, _PQ_DIM = 64, 64, 16
# edge-store rungs Mosaic refuses (cagra._NO_TPU_KERNEL_RUNGS: search
# never dispatches their kernels on TPU)
_INT4_REFUSED = pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic lowering: Shape mismatch in input, indices and output")
_PQ_REFUSED = pytest.mark.xfail(
    strict=True, reason="Mosaic: Slice shape along dimension 2 must be "
                        "aligned to tiling (128), but is 16")
# the per-rung edge store (n, deg_p, W) and its extra kernel operands
_STORES = {
    "dense": (((N, _DEG, D), jnp.int8), {}),
    "dense_pen": (((N, _DEG, D), jnp.int8), {"pen": ((N, _DEG), jnp.float32)}),
    "int4": (((N, _DEG, D // 2), jnp.int8), {}),
    "pq": (((N, _DEG, _PQ_DIM), jnp.uint8),
           {"cbm": ((_PQ_DIM * 256, D), jnp.int8),
            "cb_scale": ((1, D), jnp.float32)}),
}


def _store_args(rung):
    store, extra = _STORES[rung]
    return store, list(extra), list(extra.values())


@pytest.mark.parametrize("rung", [
    "dense", "dense_pen", pytest.param("int4", marks=_INT4_REFUSED),
    pytest.param("pq", marks=_PQ_REFUSED)])
def test_graph_expand(one_chip, rung):
    from raft_tpu.ops.graph_expand import graph_expand

    store, names, shapes = _store_args(rung)
    mode = rung.removesuffix("_pen")

    def fn(parents, q, vecs, aux, *extra):
        return graph_expand(parents, q, vecs, aux, _DEG // 2, mode=mode,
                            interpret=False, **dict(zip(names, extra)))

    _compile(one_chip, fn, ((_M, 1), jnp.int32), ((_M, D), jnp.float32),
             store, ((N, 2, _DEG), jnp.float32), *shapes)


@pytest.mark.parametrize("rung", [
    "dense", "dense_pen", pytest.param("int4", marks=_INT4_REFUSED)])
def test_cagra_fused(one_chip, rung):
    from raft_tpu.ops.cagra_fused import fused_traverse

    store, names, shapes = _store_args(rung)
    mode = rung.removesuffix("_pen")

    def fn(q, bd, bi, vecs, aux, gph, *extra):
        return fused_traverse(q, bd, bi, vecs, aux, gph, itopk=_ITOPK,
                              width=1, max_iter=8, kprime=_DEG // 2,
                              degree=_DEG, mode=mode, interpret=False,
                              **dict(zip(names, extra)))

    _compile(one_chip, fn, ((_M, D), jnp.float32),
             ((_M, _ITOPK), jnp.float32), ((_M, _ITOPK), jnp.int32),
             store, ((N, 2, _DEG), jnp.float32), ((N, _DEG), jnp.int32),
             *shapes)


def test_ring_pallas_at_vmem_cap(topo):
    """The remote-DMA ring merge over the 2x2 mesh at the largest query
    batch ``ring_capable`` admits (its VMEM cap)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from raft_tpu.ops import ring_topk
    from raft_tpu.utils import shard_map_compat

    mesh = Mesh(np.array(topo.devices), ("shard",))
    p = mesh.shape["shard"]
    m = ring_topk._VMEM_CELL_CAP // 128
    assert ring_topk.ring_capable(m, K, "tpu")

    def body(d, g):
        return ring_topk.merge(d[0], g[0], K, True, axis="shard",
                               axis_size=p, engine="ring_pallas")

    fn = shard_map_compat(body, mesh=mesh, in_specs=(P("shard"),) * 2,
                          out_specs=(P(), P()), check=False)
    spec = NamedSharding(mesh, P("shard"))
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((p, m, K), jnp.float32, sharding=spec),
        jax.ShapeDtypeStruct((p, m, K), jnp.int32, sharding=spec)
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_merge_step(one_chip):
    from raft_tpu.ops.ring_topk import merge_step

    fn = functools.partial(merge_step, k=K, engine="pallas", interpret=False)
    _compile(one_chip, fn, *([((_M, K), jnp.float32), ((_M, K), jnp.int32),
                              ((_M, K), jnp.int32)] * 2))
