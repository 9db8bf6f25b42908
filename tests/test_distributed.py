"""Distributed-engine tests: shard_map PageRank equals the single-device
engine; dry-run cells lower+compile on a small forced-device mesh.

Multi-device tests run in a SUBPROCESS because the device count must be
forced before jax initialises (conftest keeps the main process at 1
device for smoke realism).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SUB = dict(cwd=_REPO, timeout=540)


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, **_SUB)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


def test_distributed_pagerank_matches_reference():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        import repro
        from repro.graph.generators import rmat_edges
        from repro.graph.structure import from_coo
        from repro.graph.partition import partition_graph
        from repro.core.reference import static_pagerank_ref, l1_error
        from repro.dist.pagerank_dist import (build_distributed_step,
                                              distributed_in_shardings)
        from repro.launch.mesh import make_test_mesh

        edges, n = rmat_edges(8, 8, seed=5)
        g = from_coo(edges[:,0], edges[:,1], n, edge_capacity=len(edges)+8)
        mesh = make_test_mesh(8)
        m, p = mesh.shape["model"], mesh.shape["data"]
        part = partition_graph(g, m, p)
        v_pad = part.v_per_shard * m
        deg = np.zeros(n, np.int64); np.add.at(deg, edges[:,0], 1)
        inv = np.zeros(v_pad, np.float32)
        inv[:n] = 1.0/(deg+1)
        ranks0 = np.zeros(v_pad, np.float32); ranks0[:n] = 1.0/n
        seeds = np.zeros(v_pad, bool); seeds[:n] = True   # static-from-warm
        # reshape edge stripes to [M, P, E_dev]
        fn = build_distributed_step(mesh, n_vertices=n, tol=1e-9,
                                    prune=False, frontier_tol=1e-7)
        sh = distributed_in_shardings(mesh)
        args = [jnp.asarray(part.src), jnp.asarray(part.dst_local),
                jnp.asarray(part.valid), jnp.asarray(ranks0),
                jnp.asarray(inv), jnp.asarray(seeds)]
        args = [jax.device_put(a, s) for a, s in zip(args, sh)]
        ranks, iters, delta = jax.jit(fn)(*args)
        ref, _ = static_pagerank_ref(edges[:,0], edges[:,1], n, tol=1e-12)
        err = l1_error(np.asarray(ranks)[:n], ref)
        print("L1", err, "iters", int(iters))
        assert err < 5e-5, err
    """)
    assert "L1" in out


def test_distributed_stream_matches_reference():
    """api-level wiring: update_pagerank(mesh=...) replays a random-update
    stream with DF-P on the mesh; every batch's fixed point must match the
    static oracle of the mutated graph."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        import repro
        from repro.core.api import update_pagerank
        from repro.core.reference import static_pagerank_ref, l1_error
        from repro.graph.dynamic import apply_batch, make_batch_update
        from repro.graph.generators import rmat_edges, random_batch_update
        from repro.graph.structure import from_coo
        from repro.launch.mesh import make_test_mesh

        edges, n = rmat_edges(8, 8, seed=11)
        g = from_coo(edges[:,0], edges[:,1], n, edge_capacity=len(edges)+64)
        mesh = make_test_mesh(8)
        ranks = update_pagerank(g, g, None, None, "static", mesh=mesh).ranks
        for i in range(3):
            live = np.stack([np.asarray(g.src), np.asarray(g.dst)], 1)
            live = live[np.asarray(g.valid)]
            dele, ins = random_batch_update(live, n, 16, seed=i)
            upd = make_batch_update(dele, ins, 16, 16)
            g_new = apply_batch(g, upd)
            r = update_pagerank(g, g_new, upd, ranks, "frontier_prune",
                                mesh=mesh)
            sv = np.asarray(g_new.src)[np.asarray(g_new.valid)]
            dv = np.asarray(g_new.dst)[np.asarray(g_new.valid)]
            ref, _ = static_pagerank_ref(sv, dv, n, tol=1e-12)
            err = l1_error(r.ranks, ref)
            assert err < 5e-5, (i, err)
            assert int(r.iterations) > 0
            g, ranks = g_new, r.ranks
        print("STREAM OK")
    """)
    assert "STREAM OK" in out


def test_dryrun_cells_compile_on_small_mesh():
    """One representative cell per family + multi-pod pagerank."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, repro
        from repro.configs.registry import get_arch
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        mesh3 = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        cells = [("qwen2.5-3b", "decode_32k", mesh),
                 ("graphsage-reddit", "minibatch_lg", mesh),
                 ("deepfm", "train_batch", mesh),
                 ("df-pagerank", "temporal_so", mesh3)]
        for arch, shape, m in cells:
            spec = get_arch(arch)
            rec = run_cell(spec, spec.shapes[shape], m, "test")
            assert rec["status"] == "OK", rec
            assert rec["cost"].get("flops", 0) > 0
        print("ALL OK")
    """)
    assert "ALL OK" in out


def test_elastic_restore_onto_different_mesh(tmp_path):
    out = _run(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        import repro
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ft import checkpoint as ck
        state = dict(w=jnp.arange(64, dtype=jnp.float32).reshape(8, 8))
        ck.save("{tmp_path}", 1, state)
        # restore sharded onto a 2x4 mesh (different from writer's layout)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        sh = dict(w=NamedSharding(mesh, P("data", "model")))
        out = ck.restore("{tmp_path}", 1,
                         jax.eval_shape(lambda: state), sh)
        assert out["w"].sharding.num_devices == 8
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(state["w"]))
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out
