"""Roofline: 3 terms per (arch × shape × mesh) from the dry-run artifacts.

    compute    = HLO_FLOPs_per_device / peak_FLOPs          [s]
    memory     = HLO_bytes_per_device / HBM_bw              [s]
    collective = collective_bytes_per_device / link_bw      [s]

HLO terms come from the **counting-mode** lowering for LM archs (layer
scans unrolled via the L=1/L=2 delta — launch/dryrun.py) and directly from
the compiled module otherwise; XLA cost_analysis is per-device-program, so
no ÷chips is applied.  The dominant term is the bottleneck the §Perf loop
attacks.  MODEL_FLOPS is the analytic useful-work count (6·N·D dense LMs,
6·N_active·D MoE, per-family formulas below); MODEL/HLO per device catches
remat/redundancy/dispatch waste.

CPU-lowering caveat (recorded per EXPERIMENTS.md §Method): XLA:CPU
legalises bf16 arithmetic to f32, so byte-based terms are ≤2× upper
bounds for bf16 tensors; comparisons between iterations share the
pipeline, so §Perf deltas are unaffected.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, Optional

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  A TPU
# kind missing here is an error (``peaks_for``), never a default.
PEAKS = {
    "TPU v5 lite": dict(
        flops=197e12,        # bf16 FLOP/s
        hbm_bw=819e9,        # B/s
        link_bw=50e9,        # B/s per ICI link (1,600 Gbit/s over 4 links)
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip"),
}
# the chip the dry-run tables and the geometry model target
TARGET_KIND = "TPU v5 lite"
CHIPS = dict(single=256, multi=512)


def peaks_for(kind: str) -> dict:
    """Peaks of the chip ``kind`` names.  A host without a TPU (``cpu``)
    models the target chip; a TPU kind with no published row raises."""
    if kind in PEAKS:
        return PEAKS[kind]
    if kind.upper().startswith("TPU"):
        raise KeyError(f"no published peaks for device kind {kind!r}: add "
                       "them, with their source, to roofline.analysis.PEAKS")
    return PEAKS[TARGET_KIND]


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float
    peak_gib: float
    status: str
    note: str = ""

    @property
    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """compute_s / max-term: 1.0 = compute-bound at peak."""
        t = self.bound_time
        return self.compute_s / t if t > 0 else 0.0


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS (useful work) per family
# ---------------------------------------------------------------------------

def lm_model_flops(spec, cell) -> float:
    cfg = spec.config
    d = cell.dims
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = d["batch"] * d["seq"]
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = d["batch"] * d["seq"]
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention over the cache
    tokens = d["batch"]
    attn = (2.0 * cfg.n_layers * d["batch"] * d["ctx"]
            * cfg.n_heads * cfg.hd * 2)
    return 2.0 * n_active * tokens + attn


def gnn_model_flops(spec, cell) -> float:
    cfg = spec.config
    d = cell.dims
    if cell.kind == "gnn_minibatch":
        n = d["batch_nodes"] * (1 + d["fanout0"]
                                + d["fanout0"] * d["fanout1"])
        e = d["batch_nodes"] * d["fanout0"] * (1 + d["fanout1"])
    elif cell.kind == "gnn_molecule":
        n = d["n_nodes"] * d["batch"]
        e = d["n_edges"] * d["batch"]
    else:
        n, e = d["n_nodes"], d["n_edges"]
    a = spec.arch_id
    if a == "graphsage-reddit":
        din = d.get("d_feat", cfg.d_in)
        f = 2.0 * n * din * cfg.d_hidden * 2 + 2.0 * e * din
    elif a == "pna":
        f = cfg.n_layers * (2.0 * e * 2 * cfg.d_hidden * cfg.d_hidden
                            + 13 * 2.0 * n * cfg.d_hidden * cfg.d_hidden)
    elif a == "nequip":
        c = cfg.channels
        f = cfg.n_layers * e * (2.0 * cfg.n_rbf * 32 + 2.0 * 32 * 6 * c
                                + 30.0 * c)
    else:  # graphcast
        dh = cfg.d_hidden
        f = (2.0 * n * cfg.n_vars * dh
             + cfg.n_layers * (2.0 * e * 2 * dh * dh
                               + 2.0 * n // 4 * 2 * dh * dh)
             + 2.0 * n * 2 * dh * cfg.n_vars)
    return 3.0 * f if cell.kind != "serve" else f   # fwd+bwd ≈ 3× fwd


def recsys_model_flops(spec, cell) -> float:
    cfg = spec.config
    d = cell.dims
    if cell.kind == "recsys_retrieval":
        return 2.0 * d["n_candidates"] * cfg.embed_dim
    b = d["batch"]
    d_in = cfg.n_sparse * cfg.embed_dim
    dims = (d_in,) + cfg.mlp_dims + (1,)
    mlp = sum(2.0 * a * bb for a, bb in zip(dims[:-1], dims[1:]))
    fm = 4.0 * cfg.n_sparse * cfg.embed_dim
    per = mlp + fm
    return b * per * (3.0 if cell.kind == "recsys_train" else 1.0)


def pagerank_model_flops(spec, cell) -> float:
    d = cell.dims
    # per iteration: one multiply-add per edge + ~5 flops per vertex
    return 2.0 * d["edge_capacity"] + 5.0 * d["n_vertices"]


# ---------------------------------------------------------------------------
# gated-SpMV geometry model (consumed by kernels.pagerank_spmv.tune)
# ---------------------------------------------------------------------------

# fixed cost of one grid step of the frontier-gated SpMV beyond its MXU
# contraction: DMA issue, scalar-prefetch reads, revisit bookkeeping.  The
# grid is STATIC (= total entries) — excess steps stay VMEM-resident but
# still run the one-hot matmul with a zeroed payload, so per-step cost is
# paid for every entry, active or not.
SPMV_STEP_OVERHEAD_S = 1e-6

# an f32 matmul at Precision.HIGHEST runs as six bf16 passes on the MXU
# (the ``flops`` peaks above are bf16)
F32_HIGHEST_PASSES = 6

# random-access HBM traffic moves whole sectors regardless of element
# width: a gather/scatter of one f64 still transfers a 32B sector.  The
# dense XLA engine pays this on every edge (gather r/d by src, scatter-
# add by dst); the packed kernel streams contiguous lanes at element
# width — that gap, not FLOPs, is the kernel path's headroom.
GATHER_SECTOR_BYTES = 32


def dense_spmv_iteration_cost(*, num_edges: int, num_vertices: int,
                              index_bytes: float = 8.0,
                              value_bytes: float = 8.0,
                              kind: str = TARGET_KIND) -> dict:
    """Roofline terms for ONE dense XLA segment-sum PageRank iteration
    (the f64 engine's step): per edge, a random gather of the source
    contribution (one sector), the scatter-add's read+write (two
    sectors) and the sequential src/dst index stream; per vertex, ~6
    streamed f64 vectors (old/new ranks, inverse degree, frontier/prune
    masks, delta).  All traffic is charged at streaming bandwidth —
    sector inflation already accounts for the random-access penalty."""
    edge_bytes = num_edges * (3.0 * GATHER_SECTOR_BYTES + index_bytes)
    vertex_bytes = num_vertices * value_bytes * 6.0
    memory_s = (edge_bytes + vertex_bytes) / peaks_for(kind)["hbm_bw"]
    return dict(memory_s=memory_s, edge_bytes=edge_bytes,
                vertex_bytes=vertex_bytes, total_s=memory_s)


def gated_spmv_iteration_cost(*, total_entries: int, active_entries: float,
                              active_windows: float, be: int, vb: int,
                              kind: str = TARGET_KIND) -> dict:
    """Roofline terms for ONE gated-SpMV iteration at a given geometry.

    Two passes.  XLA first gathers ``w = rsc[src] * valid`` over every
    lane (src, valid and the rsc sector read, w written): ungated, and
    proportional to the packed lanes, padding included.  The kernel then
    DMAs the ``w`` and ``dst_rel`` rows of *active* entries only, plus
    the active output windows: **memory traffic is gated**, but
    **compute is not** — the grid is static at ``total_entries`` steps
    and every step runs the ``[1,BE]x[VB,BE]^T`` one-hot contraction
    (inactive steps with a zeroed payload) in f32 at
    ``Precision.HIGHEST``, i.e. ``F32_HIGHEST_PASSES`` bf16 MXU passes.  Large BE trims total
    entries (fewer wasted MXU steps + less per-step overhead); small VB
    sharpens window gating (fewer bytes per active frontier vertex) but
    multiplies the window count and hence the entry count.  The tuner
    ranks candidate geometries by ``total_s = max(compute_s, memory_s)``.
    """
    gather_bytes = total_entries * be * (4 + 4 + 4 + GATHER_SECTOR_BYTES)
    lane_bytes = active_entries * be * (4 + 4)          # w, dst_rel
    out_bytes = active_windows * vb * 4.0
    pk = peaks_for(kind)
    memory_s = (gather_bytes + lane_bytes + out_bytes) / pk["hbm_bw"]
    compute_s = total_entries * (2.0 * be * vb * F32_HIGHEST_PASSES
                                 / pk["flops"] + SPMV_STEP_OVERHEAD_S)
    return dict(compute_s=compute_s, memory_s=memory_s,
                total_s=max(compute_s, memory_s))


def model_flops(spec, cell) -> float:
    return dict(lm=lm_model_flops, gnn=gnn_model_flops,
                recsys=recsys_model_flops,
                pagerank=pagerank_model_flops)[spec.family](spec, cell)


# ---------------------------------------------------------------------------
# table builder
# ---------------------------------------------------------------------------

def _whatif(spec, rec) -> str:
    """One sentence: what would move the dominant term down."""
    hints = {
        ("lm", "compute"): "raise MXU utilisation: fuse GQA head padding "
                           "(heads % 16), larger per-device microbatch",
        ("lm", "memory"): "bf16 end-to-end + fused attention kernel to cut "
                          "HBM traffic; re-check remat policy",
        ("lm", "collective"): "sequence-parallel reduce-scatter instead of "
                              "TP all-reduce; overlap with compute via "
                              "async collectives",
        ("gnn", "memory"): "frontier-gated SpMM kernel (kernels/segment_ops)"
                           " + cache blocking of node features",
        ("gnn", "collective"): "partition by dst-range (2D) to turn gather "
                               "all-reduces into model-axis all-gathers",
        ("gnn", "compute"): "segment-matmul (MXU scatter) instead of "
                            "scalar segment-sum",
        ("recsys", "memory"): "row-sharded embedding gather is HBM-bound: "
                              "pack multi-field lookups into one gather",
        ("recsys", "collective"): "shard batch over all axes; keep tables "
                                  "model-sharded to avoid replication",
        ("recsys", "compute"): "batch small MLP GEMMs",
        ("pagerank", "collective"): "all-gather only ACTIVE dst-window "
                                    "slices of R (frontier-compressed "
                                    "gather)",
        ("pagerank", "memory"): "block-gated SpMV skips inactive windows "
                                "(kernels/pagerank_spmv)",
        ("pagerank", "compute"): "closed-form DF-P update trims iterations",
    }
    return hints.get((spec.family, rec), "")


def build_table(results_dir: str = "results") -> list[RooflineRow]:
    from repro.configs.registry import REGISTRY
    rows = []
    for mesh_name in ("single", "multi"):
        path = os.path.join(results_dir, f"dryrun_{mesh_name}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            records = json.load(f)
        for r in records:
            spec = REGISTRY[r["arch"]]
            cell = spec.shapes[r["shape"]]
            if r["status"] != "OK":
                rows.append(RooflineRow(
                    r["arch"], r["shape"], mesh_name, 0, 0, 0, "-", 0, 0, 0,
                    0, r["status"], r.get("skip_reason",
                                          r.get("error", ""))[:90]))
                continue
            cost = r.get("cost_counting") or r.get("cost", {})
            coll = r.get("collectives_counting") or r.get("collectives", {})
            flops = float(cost.get("flops", 0.0))
            byts = float(cost.get("bytes accessed", 0.0))
            cbytes = float(coll.get("total", 0.0))
            pk = PEAKS[TARGET_KIND]
            comp = flops / pk["flops"]
            mem = byts / pk["hbm_bw"]
            col = cbytes / pk["link_bw"]
            dom = max((comp, "compute"), (mem, "memory"),
                      (col, "collective"))[1]
            mf = model_flops(spec, cell) / CHIPS[mesh_name]
            rows.append(RooflineRow(
                r["arch"], r["shape"], mesh_name, comp, mem, col, dom, mf,
                flops, (mf / flops if flops else 0.0),
                r.get("memory", {}).get("peak_per_device_bytes", 0) / 2**30,
                "OK", _whatif(spec, dom)))
    return rows


def to_markdown(rows: list[RooflineRow]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | MODEL/HLO | peak GiB | note |\n"
           "|---|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        if r.status != "OK":
            lines.append(f"| {r.arch} | {r.shape} | {r.mesh} | - | - | - | "
                         f"{r.status} | - | - | {r.note} |")
            continue
        lines.append(
            f"| {r.arch} | {r.shape} | {r.mesh} | {r.compute_s:.3e} | "
            f"{r.memory_s:.3e} | {r.collective_s:.3e} | **{r.dominant}** | "
            f"{r.useful_ratio:.2f} | {r.peak_gib:.2f} | {r.note} |")
    return "\n".join(lines)


if __name__ == "__main__":
    rows = build_table()
    print(to_markdown(rows))
