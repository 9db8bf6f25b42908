"""Per-kernel validation: shape/dtype sweeps, interpret-mode vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pagerank as pr
from repro.core.kernel_engine import df_pagerank_kernel
from repro.core.reference import l1_error, static_pagerank_ref
from repro.graph.dynamic import apply_batch, make_batch_update
from repro.graph.generators import (erdos_renyi_edges, random_batch_update,
                                    rmat_edges)
from repro.graph.structure import from_coo
from repro.kernels.pagerank_spmv.ops import gated_contrib, pack_blocks
from repro.kernels.pagerank_spmv.ref import frontier_spmv_ref
from repro.kernels.segment_ops.ops import aggregate_features


def _dense_contrib(edges, n, rsc, awin, vb):
    dense = np.zeros(n, np.float32)
    np.add.at(dense, edges[:, 1], rsc[edges[:, 0]])
    return np.where(np.repeat(awin, vb)[:n], dense, 0)


@pytest.mark.parametrize("be,vb", [(128, 128), (256, 128), (512, 256),
                                   (1024, 512)])
@pytest.mark.parametrize("gen", ["rmat", "er"])
def test_spmv_kernel_shape_sweep(be, vb, gen):
    if gen == "rmat":
        edges, n = rmat_edges(8, 8, seed=be + vb)
    else:
        edges, n = erdos_renyi_edges(500, 4000, seed=be)
    packed = pack_blocks(edges[:, 0], edges[:, 1],
                         np.ones(len(edges), bool), n, be=be, vb=vb)
    rng = np.random.default_rng(be)
    ranks = jnp.asarray(rng.random(n))
    deg = np.zeros(n, np.int64)
    np.add.at(deg, edges[:, 0], 1)
    inv_deg = jnp.asarray(1.0 / (deg + 1))
    for frac in (1.0, 0.25, 0.0):
        aff = jnp.asarray(rng.random(n) < frac)
        out_k = gated_contrib(packed, ranks, inv_deg, aff, use_kernel=True)
        out_r = gated_contrib(packed, ranks, inv_deg, aff, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-4, atol=1e-6)
        nw = packed.num_windows
        affp = np.zeros(nw * vb, bool)
        affp[:n] = np.asarray(aff)
        awin = affp.reshape(nw, vb).any(1)
        rsc = np.asarray((ranks * inv_deg).astype(jnp.float32))
        dense = _dense_contrib(edges, n, rsc, awin, vb)
        np.testing.assert_allclose(np.asarray(out_k), dense,
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmv_kernel_dtype_sweep(dtype):
    edges, n = rmat_edges(7, 8, seed=11)
    packed = pack_blocks(edges[:, 0], edges[:, 1],
                         np.ones(len(edges), bool), n, be=128, vb=128)
    rng = np.random.default_rng(3)
    v_pad = packed.num_windows * packed.vb
    rsc = jnp.asarray(rng.random(v_pad), dtype)
    awin = jnp.ones((packed.num_windows,), bool)
    from repro.kernels.pagerank_spmv.pagerank_spmv import frontier_spmv
    out = frontier_spmv(packed, rsc, awin, interpret=True)
    ref = frontier_spmv_ref(packed.src, packed.dst_rel, packed.valid,
                            packed.window, rsc.astype(jnp.float32), awin,
                            n, packed.vb)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_spmv_empty_graph():
    packed = pack_blocks(np.zeros(0, np.int32), np.zeros(0, np.int32),
                         np.zeros(0, bool), 128, be=128, vb=128)
    out = gated_contrib(packed, jnp.ones(128), jnp.ones(128),
                        jnp.ones(128, bool), use_kernel=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


@pytest.mark.parametrize("d", [16, 64, 130])
def test_spmm_kernel_feature_dims(d):
    edges, n = rmat_edges(7, 6, seed=d)
    packed = pack_blocks(edges[:, 0], edges[:, 1],
                         np.ones(len(edges), bool), n, be=128, vb=128)
    rng = np.random.default_rng(d)
    feats = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    aff = jnp.asarray(rng.random(n) < 0.5)
    a = aggregate_features(packed, feats, aff, use_kernel=True)
    b = aggregate_features(packed, feats, aff, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_kernel_engine_df_matches_f64_engine():
    """End-to-end: Pallas-path DF fixed point ≈ XLA f64 DF fixed point."""
    edges, n = rmat_edges(8, 8, seed=21)
    g = from_coo(edges[:, 0], edges[:, 1], n, edge_capacity=len(edges) * 2)
    res0 = pr.static_pagerank(g)
    dele, ins = random_batch_update(edges, n, 12, seed=22)
    upd = make_batch_update(dele, ins, 32, 32)
    g2 = apply_batch(g, upd)
    sv = np.asarray(g2.src)[np.asarray(g2.valid)]
    dv = np.asarray(g2.dst)[np.asarray(g2.valid)]
    packed = pack_blocks(sv, dv, np.ones(len(sv), bool), n, be=256, vb=128)
    from repro.graph.dynamic import touched_vertices_mask
    touched = touched_vertices_mask(upd, n)
    resk = df_pagerank_kernel(g, g2, packed, touched, res0.ranks,
                              tol=1e-7, frontier_tol=1e-5)
    ref, _ = static_pagerank_ref(sv, dv, n, tol=1e-14)
    assert l1_error(resk.ranks, ref) < 5e-5   # f32 path tolerance


@pytest.mark.parametrize("be,vb", [(128, 128), (512, 256), (2048, 256)])
def test_spmv_kernel_bitwise_matches_ref(be, vb):
    """Interpret-mode kernel == ref.py bit for bit on inputs whose sums
    are exact in f32 (multiples of 2^-8 below 1): any summation order
    gives the same bits, so gather, gating, windowing and the one-hot
    scatter are checked exactly, not to a tolerance."""
    from repro.kernels.pagerank_spmv.pagerank_spmv import \
        frontier_spmv_padded
    from repro.kernels.pagerank_spmv.ref import frontier_spmv_ref_padded
    edges, n = rmat_edges(11, 8, seed=be)
    packed = pack_blocks(edges[:, 0], edges[:, 1],
                         np.ones(len(edges), bool), n, be=be, vb=vb,
                         spill_lanes_per_window=16)
    rng = np.random.default_rng(vb)
    v_pad = packed.num_windows * vb
    rsc = jnp.asarray(rng.integers(0, 256, v_pad) / 256.0, jnp.float32)
    for frac in (1.0, 0.3, 0.0):
        awin = jnp.asarray(rng.random(packed.num_windows) < frac)
        out = frontier_spmv_padded(packed, rsc, awin, interpret=True)
        ref = frontier_spmv_ref_padded(packed.src, packed.dst_rel,
                                       packed.valid, packed.window, rsc,
                                       awin, vb)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
