"""The serving path's kernels compile for a TPU v5e chip.

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, unsupported vector ops, more memory than the
device has).  Interpret-mode tests cannot see any of that.  Nothing runs
here, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401
from repro.core.api import KERNEL_FLAGS
from repro.graph.dynamic import BatchUpdate
from repro.graph.structure import EdgeListGraph
from repro.kernels.pagerank_spmv import ops
from repro.kernels.pagerank_spmv.pagerank_spmv import (PackedGraph,
                                                       frontier_spmv_padded)

HBM_BYTES = 16 * 10**9          # one v5e chip
WIKI_TALK = (1_140_149, 7_833_140)   # paper Table 1: |V|, |E_T|


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(sharding, v, e, be, vb, overlay=1024):
    """Abstract PackedGraph for v vertices and e lanes of edges, with one
    spill entry per window (the serving pack's layout)."""
    nw = -(-v // vb)
    ne = -(-e // be) + nw
    s = lambda shape, dt: _sds(sharding, shape, dt)          # noqa: E731
    return PackedGraph(
        src=s((ne, be), jnp.int32), dst_rel=s((ne, be), jnp.int32),
        valid=s((ne, be), jnp.float32), window=s((ne,), jnp.int32),
        entry_start=s((nw + 1,), jnp.int32),
        sorted_key=s((ne * be,), jnp.int64),
        sorted_lane=s((ne * be,), jnp.int32),
        ovl_key=s((overlay,), jnp.int64), ovl_lane=s((overlay,), jnp.int32),
        num_vertices=v, vb=vb, be=be, max_entries_per_window=ne)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return used < HBM_BYTES, used


@pytest.mark.parametrize("v,e", [(131_072, 1 << 20), WIKI_TALK],
                         ids=["v131072", "wiki-talk"])
def test_spmv_kernel_compiles_for_v5e(one_chip, v, e):
    packed = _packed(one_chip, v, e, be=512, vb=256)
    v_pad = packed.num_windows * packed.vb
    compiled = frontier_spmv_padded.lower(
        packed, _sds(one_chip, (v_pad,), jnp.float32),
        _sds(one_chip, (packed.num_windows,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ok, used = _fits(compiled)
    assert ok, used


def test_fused_update_sweep_compiles_for_v5e(one_chip, monkeypatch):
    """The kernel engine's serving program (packed update + the whole f32
    loop, core.kernel_engine._fused_update_loop) at wiki-talk size."""
    from repro.core.kernel_engine import _fused_update_loop
    # the engine asks the process's backend whether to interpret the
    # kernel; this process's backend is the CPU, the target is the chip
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    v, e = WIKI_TALK
    s = lambda shape, dt: _sds(one_chip, shape, dt)          # noqa: E731
    graph = EdgeListGraph(src=s((e,), jnp.int32), dst=s((e,), jnp.int32),
                          valid=s((e,), jnp.bool_), num_vertices=v,
                          num_edges=s((), jnp.int32))
    cap = 256                                   # ingest batch capacity
    update = BatchUpdate(*(s((cap,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.int32, jnp.bool_)))
    packed = _packed(one_chip, v, e, be=512, vb=256, overlay=64 * cap)
    compiled = _fused_update_loop.lower(
        graph, packed, update, s((v,), jnp.float64), s((v,), jnp.bool_),
        use_kernel=True, **KERNEL_FLAGS["frontier_prune"]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ok, used = _fits(compiled)
    assert ok, used
