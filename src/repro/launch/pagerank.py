"""End-to-end dynamic-PageRank streaming driver (the paper's workload).

Replays a temporal stream (paper §5.1.4: 90% preload + consecutive
batches), maintains ranks with the chosen approach, checkpoints
(ranks, batch_idx) for restart, reports per-batch runtime/error/work.

    PYTHONPATH=src python -m repro.launch.pagerank \
        --dataset sx-mathoverflow --method frontier_prune --batches 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.core.api import METHODS, update_pagerank
from repro.core.reference import l1_error, static_pagerank_ref
from repro.data.snap import PAPER_TABLE1, load_temporal
from repro.ft.checkpoint import CheckpointManager
from repro.graph.dynamic import apply_batch, make_batch_update
from repro.graph.generators import TemporalStream
from repro.graph.structure import from_coo
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_test_mesh


MESHES = ("none", "test", "production")


def _resolve_mesh(name: str):
    """--mesh none|test|production -> jax Mesh (or None for single-device).

    ``test`` sizes itself to the visible devices (force more with
    XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU).
    """
    if name == "none":
        return None
    if name == "test":
        return make_test_mesh(len(jax.devices()))
    return make_production_mesh()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sx-mathoverflow",
                    choices=list(PAPER_TABLE1))
    ap.add_argument("--method", default="frontier_prune", choices=METHODS)
    ap.add_argument("--batch-frac", type=float, default=1e-3)
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_pr_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--check-error", action="store_true")
    ap.add_argument("--mesh", choices=MESHES,
                    default="none",
                    help="replay the stream on a multi-device mesh via the "
                         "shard_map engine (repro.dist.pagerank_dist)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = _resolve_mesh(args.mesh)
    if mesh is not None:
        print(f"mesh {dict(mesh.shape)} over {len(jax.devices())} devices")
    ds = load_temporal(args.dataset)
    print(f"dataset {ds.name}: |V|={ds.num_vertices:,} "
          f"|E_T|={len(ds.edges):,} synthetic={ds.synthetic}")
    stream = TemporalStream(ds.edges, ds.num_vertices, args.batch_frac,
                            args.batches)
    pre = stream.preload_edges()
    cap = len(pre) + stream.batch_size * stream.num_batches + 64
    graph = from_coo(pre[:, 0], pre[:, 1], ds.num_vertices,
                     edge_capacity=cap)
    print(f"preloaded {int(graph.num_valid_edges()):,} static edges; "
          f"{stream.num_batches} batches of {stream.batch_size}")

    res = update_pagerank(graph, graph, None, None, "static", mesh=mesh)
    ranks = res.ranks
    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
    state_t = dict(ranks=jax.ShapeDtypeStruct((ds.num_vertices,),
                                              jnp.float64),
                   batch_idx=jax.ShapeDtypeStruct((), jnp.int64))
    step0, restored = mgr.restore_latest(state_t)
    start = 0
    if restored is not None:
        ranks = restored["ranks"]
        start = int(restored["batch_idx"])
        print(f"restored at batch {start}")
        for i in range(start):      # replay graph structure to batch start
            upd = make_batch_update(np.zeros((0, 2)), stream.batch(i), 8,
                                    max(8, stream.batch_size))
            graph = apply_batch(graph, upd)

    for i in range(start, stream.num_batches):
        upd = make_batch_update(np.zeros((0, 2)), stream.batch(i), 8,
                                max(8, stream.batch_size))
        t0 = time.perf_counter()
        graph_new = apply_batch(graph, upd)
        r = update_pagerank(graph, graph_new, upd, ranks, args.method,
                            mesh=mesh)
        jax.block_until_ready(r.ranks)
        dt = time.perf_counter() - t0
        msg = (f"batch {i:3d}: {dt*1e3:7.1f} ms  iters={int(r.iterations):3d}"
               f"  affected={int(jnp.sum(r.affected_ever)):,}"
               f"  edges={int(r.edges_processed):,}")
        if args.check_error:
            sv = np.asarray(graph_new.src)[np.asarray(graph_new.valid)]
            dv = np.asarray(graph_new.dst)[np.asarray(graph_new.valid)]
            ref, _ = static_pagerank_ref(sv, dv, ds.num_vertices, tol=1e-14)
            msg += f"  L1err={l1_error(r.ranks, ref):.2e}"
        print(msg, flush=True)
        graph, ranks = graph_new, r.ranks
        mgr.maybe_save(i + 1, dict(ranks=ranks,
                                   batch_idx=jnp.asarray(i + 1)))
    print("stream complete")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
