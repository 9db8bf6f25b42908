"""Frontier-block-gated SpMV — the paper's work-skipping, TPU-native.

The paper skips *vertices* that are not affected (OpenMP dynamic schedule).
A TPU cannot branch per vertex, but it can skip whole VMEM tiles.  We
therefore translate "process only affected vertices" into "DMA + compute
only **active dst windows**":

  * edges are dst-sorted and packed into entries of BE edges, each entry
    belonging to one dst *window* of VB consecutive vertices
    (``pack_blocks``, host-side, done once per batch update);
  * a window is *active* iff any of its VB vertices is affected;
  * XLA gathers the per-lane source weights ``w = rsc[src] * valid``
    ahead of the kernel (Mosaic has no general vector gather); this
    pass reads every lane and is not gated;
  * the grid visits a **compacted list of active entries** delivered via
    scalar prefetch; the BlockSpec index_map reads the entry id from SMEM,
    so the ``w``/``dst_rel`` rows of inactive entries are never DMA'd
    from HBM — the kernel's memory traffic is O(active_edges);
  * excess grid steps (grid is static = NE) re-map to the last active entry
    — its block stays VMEM-resident, so they cost no HBM traffic; their
    contribution is zeroed via the ``i < n_active`` predicate;
  * the scatter within a window is a one-hot matmul
    ``w[1,BE] · onehot[VB,BE]ᵀ`` — an MXU contraction, the canonical TPU
    scatter idiom (VB=256 keeps the lane dim a multiple of 128, BE=2048
    mirrors the paper's OpenMP chunk size);
  * per-window accumulation across an entry run uses the Pallas revisit
    pattern: first entry of a run overwrites, the rest accumulate.

dtypes: f32 (primary) and bf16 (with f32 MXU accumulation).  f64 stays on
the XLA path — the TPU MXU has no f64; DESIGN.md §3 records the trade-off.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BE = 2048     # edges per entry (paper's OpenMP chunk size)
DEFAULT_VB = 256      # vertices per dst window (2 × 128 lanes)


LANE_SENTINEL = np.iinfo(np.int64).max   # key of a never-live lane

# block index constant for index_maps: a bare 0 becomes int64 under the
# package-wide x64 mode, which Mosaic cannot lower
_I0 = np.int32(0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedGraph:
    """Blocked edge structure: host-packed bootstrap, device-maintained.

    Entries stay sorted by window (same-window entries are contiguous in
    flat order — the kernel's overwrite/accumulate run detection depends
    on it) and ``window``/``entry_start`` never change after packing;
    incremental updates (``update.apply_batch_packed``) only flip lanes.

    The last four arrays form the *edge locator* the incremental update
    searches instead of scanning lanes: ``sorted_key``/``sorted_lane``
    index the pack-time lanes by (src·V + dst) key for binary search, and
    ``ovl_key``/``ovl_lane`` are an append-only overlay recording every
    lane claimed by an insertion since the last pack.  Locator hits are
    *candidates* — a lane may have been freed and reclaimed for another
    edge — so lookups verify the lane's current contents; every live edge
    is findable through one of the two (pack-time lanes via the base
    index, inserted lanes via the overlay).  A full overlay is a checked
    error that callers resolve by repacking (which rebuilds the base
    index and empties the overlay).
    """

    src: jax.Array        # int32[NE, BE]
    dst_rel: jax.Array    # int32[NE, BE]   dst - window*VB
    valid: jax.Array      # f32[NE, BE]     1.0 live / 0.0 pad
    window: jax.Array     # int32[NE]       window id per entry
    entry_start: jax.Array  # int32[NW+1]   window w owns entries
    #                       [entry_start[w], entry_start[w+1])
    sorted_key: jax.Array   # int64[NE*BE]  pack-time lane keys, ascending
    sorted_lane: jax.Array  # int32[NE*BE]  flat lane id per sorted key
    ovl_key: jax.Array      # int64[K]      keys inserted since the pack
    ovl_lane: jax.Array     # int32[K]      lane each insertion claimed
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    vb: int = dataclasses.field(metadata=dict(static=True))
    be: int = dataclasses.field(metadata=dict(static=True))
    # max entries any one window owns — bounds the per-window free-slot
    # scan of the incremental update (static so gather shapes stay fixed)
    max_entries_per_window: int = dataclasses.field(
        default=1, metadata=dict(static=True))

    @property
    def num_entries(self) -> int:
        return self.src.shape[0]

    @property
    def num_windows(self) -> int:
        return -(-self.num_vertices // self.vb)

    @property
    def overlay_capacity(self) -> int:
        return self.ovl_key.shape[0]


def pack_blocks(src: np.ndarray, dst: np.ndarray, valid: np.ndarray,
                num_vertices: int, be: int = DEFAULT_BE,
                vb: int = DEFAULT_VB, num_entries: int | None = None,
                spill_lanes_per_window: int = 0,
                extra_entries: int = 0,
                overlay_capacity: int = 1024,
                max_entries_per_window: int | None = None) -> PackedGraph:
    """Group live edges by dst window, split each group into BE-edge entries.

    Fully vectorised (one stable argsort + one scatter — no Python loop
    over windows, empty or not).  ``num_entries`` pins the entry capacity
    so a temporal stream keeps one compiled kernel across batches; excess
    capacity is appended as empty entries owned by the *last* window so
    the window array stays sorted (a window-0 tail would break the
    kernel's first-entry-of-run overwrite when window 0 is active).

    ``spill_lanes_per_window`` guarantees every window owns at least that
    many free (padded) lanes, adding whole empty entries where the last
    partial entry's slack is not enough — headroom for
    ``update.apply_batch_packed`` to claim insertion slots without a host
    repack.  Windows with no edges get entries too, so every window is
    insertable and every active window has a block the kernel writes.

    ``extra_entries`` (ignored when ``num_entries`` pins the capacity)
    appends that many additional empty tail entries, owned by the *last*
    window until a repack at the same total capacity redistributes them
    to whichever windows grew — size it to match the edge list's spare
    ``edge_capacity`` so repacks keep fitting as the graph grows (the
    spill guarantee itself may stop fitting under skewed growth; stream
    owners degrade it on repack, see ``serve.engine.ServeEngine``).

    ``overlay_capacity`` sizes the insertion overlay of the edge locator
    (see ``PackedGraph``): how many insertions ``apply_batch_packed`` can
    absorb before the stream owner must repack.

    ``max_entries_per_window`` pins the static per-window entry bound (a
    jit shape): a stream owner repacking mid-stream must pass the value
    pinned at bootstrap or the compiled update/kernel retrace.  It must
    cover the widest window of *this* pack (checked); ``num_entries``
    (every window can at most own all entries) is always a safe pin.
    """
    src = np.asarray(src, np.int32)[np.asarray(valid, bool)]
    dst = np.asarray(dst, np.int32)[np.asarray(valid, bool)]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    win = dst // vb
    nw = -(-num_vertices // vb)

    counts = np.bincount(win, minlength=nw).astype(np.int64)
    n_base = -(-counts // be)                        # ceil, 0 for empty
    slack = n_base * be - counts
    need = np.maximum(0, spill_lanes_per_window - slack)
    n_w = n_base + -(-need // be)                    # entries per window
    offsets = np.concatenate([[0], np.cumsum(n_w)])
    ne = int(offsets[-1])
    cap = (num_entries if num_entries is not None
           else max(ne + max(0, extra_entries), 1))
    if ne > cap:
        raise ValueError(
            f"{ne} entries exceed capacity {cap}; raise num_entries or "
            "shrink spill_lanes_per_window (capacity sizing: DESIGN.md §8)")

    s = np.zeros((cap, be), np.int32)
    d = np.zeros((cap, be), np.int32)
    v = np.zeros((cap, be), np.float32)
    # rank of each (dst-sorted) edge within its window -> (entry, lane)
    edge_start = np.concatenate([[0], np.cumsum(counts)])[:-1]
    rank = np.arange(len(dst), dtype=np.int64) - edge_start[win]
    entry_idx = offsets[win] + rank // be
    lane_idx = rank % be
    s[entry_idx, lane_idx] = src
    d[entry_idx, lane_idx] = dst - win * vb
    v[entry_idx, lane_idx] = 1.0

    # entry -> window map; capacity tail belongs to the last window
    window = np.full(cap, nw - 1, np.int32)
    window[:ne] = np.repeat(np.arange(nw, dtype=np.int32), n_w)
    entry_start = offsets.astype(np.int32).copy()
    entry_start[nw] = cap
    owned = np.diff(entry_start.astype(np.int64))

    # edge locator: pack-time lanes sorted by key + an empty overlay
    lane_key = np.full(cap * be, LANE_SENTINEL, np.int64)
    flat = entry_idx * be + lane_idx
    lane_key[flat] = src.astype(np.int64) * num_vertices + dst
    order = np.argsort(lane_key)
    widest = max(1, int(owned.max()))
    if max_entries_per_window is None:
        max_entries_per_window = widest
    elif widest > max_entries_per_window:
        raise ValueError(
            f"{widest} entries in one window exceed the pinned "
            f"max_entries_per_window {max_entries_per_window}")
    return PackedGraph(
        src=jnp.asarray(s),
        dst_rel=jnp.asarray(d),
        valid=jnp.asarray(v),
        window=jnp.asarray(window),
        entry_start=jnp.asarray(entry_start),
        sorted_key=jnp.asarray(lane_key[order]),
        sorted_lane=jnp.asarray(order.astype(np.int32)),
        ovl_key=jnp.full((overlay_capacity,), LANE_SENTINEL, jnp.int64),
        ovl_lane=jnp.zeros((overlay_capacity,), jnp.int32),
        num_vertices=num_vertices, vb=vb, be=be,
        max_entries_per_window=max_entries_per_window)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _kernel(sel_ref, win_ref, first_ref, nact_ref,     # scalar prefetch
            w_ref, dstrel_ref,                          # tensor in [1, BE]
            out_ref):                                   # tensor out [1, VB]
    i = pl.program_id(0)
    active = (i < nact_ref[0]).astype(jnp.float32)
    be, vb = w_ref.shape[-1], out_ref.shape[-1]
    w = w_ref[...] * active                              # [1, BE]
    # transposed one-hot [VB, BE]: dst_rel stays a lane-major row, and the
    # MXU contracts both operands on their lane dimension
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (vb, be), 0)
              == dstrel_ref[...]).astype(jnp.float32)
    part = jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [1, VB]

    @pl.when(first_ref[i] == 1)
    def _write():
        out_ref[...] = part

    @pl.when(first_ref[i] == 0)
    def _accum():
        out_ref[...] += part


@partial(jax.jit, static_argnames=("interpret",))
def frontier_spmv_padded(packed: PackedGraph, rsc: jax.Array,
                         active_window: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """Gated blocked SpMV on pre-padded buffers.  Returns f32[V_pad]
    contributions (V_pad = NW*VB); inactive windows are zeroed.

    rsc: f32/bf16[>= V_pad] scaled ranks R/d, indexed by ``packed.src``;
    active_window: bool[NW], precomputed by the caller.  A shard-local
    pack (shard.py) passes the full replicated vector, since its ``src``
    stays global while its windows are local.

    The per-lane gather ``w = rsc[src] * valid`` runs in XLA ahead of the
    kernel: Mosaic has no general vector gather, and the kernel then
    needs no whole-vector VMEM block.  The kernel reads the ``w`` and
    ``dst_rel`` rows of active entries only (DESIGN.md §8).
    """
    ne, be = packed.src.shape
    vb = packed.vb
    nw = packed.num_windows
    w = jnp.take(rsc, packed.src, axis=0).astype(jnp.float32) * packed.valid

    # --- device-side active-entry compaction (stable order) ---------------
    # active entries first, original order kept within each group: a
    # prefix-sum permutation (a stable argsort compiles for tens of
    # seconds on TPU at a million vertices)
    entry_active = active_window[packed.window]
    n_on = jnp.cumsum(entry_active.astype(jnp.int32))
    nact = n_on[-1]
    idx = jnp.arange(ne, dtype=jnp.int32)
    pos = jnp.where(entry_active, n_on - 1, nact + idx - n_on)
    sel = jnp.zeros((ne,), jnp.int32).at[pos].set(idx)
    win_sel = packed.window[sel]
    # windows of excess steps are pinned to the last active entry's window
    last = jnp.maximum(nact - 1, 0)
    pin = win_sel[last]
    win_eff = jnp.where(idx < nact, win_sel, pin)
    sel_eff = jnp.where(idx < nact, sel, sel[last])
    first = jnp.where(
        idx < nact,
        jnp.concatenate([jnp.ones((1,), jnp.int32),
                         (win_eff[1:] != win_eff[:-1]).astype(jnp.int32)]),
        0)
    # i==0 must write even when nact==0 (zeros) so block 0 is defined
    first = first.at[0].set(1)
    nact_arr = jnp.asarray([nact], jnp.int32)

    # one entry (row) per block: rows go 3-D so a block's last two dims
    # equal the array's, as Mosaic requires of a (1, BE) tile
    row = pl.BlockSpec((None, 1, be),
                       lambda i, sel, win, first, nact: (sel[i], _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ne,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec(
            (None, 1, vb),
            lambda i, sel, win, first, nact: (win[i], _I0, _I0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nw, 1, vb), jnp.float32),
        name="frontier_spmv",
        interpret=interpret,
    )(sel_eff, win_eff, first, nact_arr,
      w.reshape(ne, 1, be), packed.dst_rel.reshape(ne, 1, be))
    # inactive windows are never visited -> their blocks are undefined;
    # the contract (and the engine) wants zeros there.
    vmask = jnp.repeat(active_window, vb)
    return jnp.where(vmask, out.reshape(-1), 0.0)


@partial(jax.jit, static_argnames=("interpret",))
def frontier_spmv(packed: PackedGraph, rsc: jax.Array,
                  active_window: jax.Array, *, interpret: bool = False
                  ) -> jax.Array:
    """Gated blocked SpMV.  Returns f32[num_vertices] contributions."""
    return frontier_spmv_padded(packed, rsc, active_window,
                                interpret=interpret)[: packed.num_vertices]
