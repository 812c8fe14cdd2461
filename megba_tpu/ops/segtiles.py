"""Block-aligned tiled segment reduction / expansion (the TPU scatter killer).

The reference accumulates per-edge Hessian contributions with CUDA
atomicAdd (src/edge/build_linear_system.cu:88-146) and applies the
coupling blocks with cuSPARSE SpMV / per-edge scatter kernels
(src/solver/implicit_schur_pcg_solver.cu:20-90).  The direct XLA
translation — `out.at[:, idx].add(rows)` — is catastrophic on TPU:
XLA:TPU lowers scatter-add to a serialized per-update loop (~45 ns per
edge measured on a v5e), which puts sixty full-edge-axis scatters per LM
iteration on the critical path.

This module replaces every large gather/scatter with dense one-hot
matmuls that ride the MXU, organised by a host-side *plan*:

  1. Sort edges by segment (camera or point id) and PAD so that each
     tile of `tile` consecutive edge slots touches segments from exactly
     ONE aligned block of `block` segments.  Padding slots carry zero
     data, so they are inert in every reduction.
  2. `tile_reduce`: a Pallas grid over tiles; each tile computes
     `data[F, T] @ onehot[T, B] -> [F, B]` in VMEM and accumulates into
     the output block `[F, B]` shared by consecutive tiles (the per-tile
     block index is non-decreasing by construction, so revisits are
     always consecutive — the canonical Pallas accumulation pattern).
     The output is written exactly once per block: no scatter exists.
  3. `tile_expand`: the transpose — `table[F, B] @ onehot[B, T]` —
     replaces `jnp.take(table, idx, axis=1)` (segment -> edge gather).

The one-hot spans a tile's *window*, not its whole block: a tile's slots
hold few consecutive segment ids when each segment has several edges,
so each tile gets `tile_lo` (its smallest local id rounded down to a
lane multiple of 128, clamped so the window ends inside the block) and
the plan one static `window` (the narrowest lane multiple covering every
tile from its `tile_lo`), both read off the plan's own index stream
(`tile_windows`).  The kernels then build `onehot[window, T]` from
`local - tile_lo` and add (reduce) or read (expand) the block at lane
offset `tile_lo`; the skipped one-hot entries are exact zeros.  The
plan's sparsest tile sets the window of all its tiles.  On the venice
benchmark graph (5-6 edges a point, B 2048, T 1024) the 4,899 point
tiles span 206 ids each and the window is 384, a fifth of the block;
when every segment has at least 2 edges a tile spans at most T/2 + 1
ids, so the window is at most 640.  Camera blocks (B 128) and plans whose
tiles span their block take `window == block` and `tile_lo` 0.

Everything is feature-major ([F, N] rows, see core/fm.py).  A pure-XLA
fallback with identical semantics (`reduce_fallback` / `expand_fallback`)
runs the same plan on CPU / in tests and under the sharded mesh path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megba_tpu.ops import fused as fused_ops

# Defaults chosen for v5e VMEM (~128 MB) and MXU tile shapes:
# onehot [T, B] f32 must stay a few MB.  The camera axis is short
# (thousands), so narrow blocks waste nothing; the point axis is long
# (millions) with ~5 edges per point, so B ~ 2 * T keeps the padding
# overhead ~10% while amortising block switches.
DEFAULT_TILE_CAM = 2048
DEFAULT_BLOCK_CAM = 128
DEFAULT_TILE_PT = 1024
DEFAULT_BLOCK_PT = 2048
LANES = 128  # vreg lane width: one-hot windows start and end on it


def _fit_tile(t: int, n: int) -> int:
    """Shrink tile size t so it does not dwarf an n-edge problem."""
    while t > 128 and t >= 4 * n:
        t //= 2
    return t


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Static reordering of one edge axis for block-aligned reduction.

    All index arrays are host numpy; callers move them on-device once at
    lowering.  `perm[s]` is the source edge for slot s (padding slots
    repeat a valid source and are masked).  `n_slots = n_tiles * tile`.
    """

    tile: int
    block: int
    num_segments: int  # true segment count (outputs sliced to this)
    num_blocks: int
    n_edges: int  # real edges (before padding)
    perm: np.ndarray  # [n_slots] int32 source edge per slot
    seg: np.ndarray  # [n_slots] int32 segment id per slot (in-block valid)
    local: np.ndarray  # [n_slots] int32 seg - block_base, in [0, block)
    mask: np.ndarray  # [n_slots] float32 1.0 real / 0.0 padding
    tile_block: np.ndarray  # [n_tiles] int32 block index per tile
    tile_first: np.ndarray  # [n_tiles] int32 1 if first tile of its block
    tile_lo: np.ndarray  # [n_tiles] int32 first local id of the tile's window
    window: int  # one-hot width: every slot's local - tile_lo in [0, window)

    @property
    def n_slots(self) -> int:
        return self.perm.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.tile_block.shape[0]

    @property
    def padded_segments(self) -> int:
        return self.num_blocks * self.block


def build_tile_plan(
    idx: np.ndarray,
    num_segments: int,
    tile: int,
    block: int,
) -> TilePlan:
    """Plan a block-aligned order for edges with segment ids `idx`.

    Stable-sorts edges by segment, then pads each aligned block of
    `block` segments to a whole number of `tile`-edge tiles.  Every
    block gets at least one tile (possibly all-padding) so the kernel
    initialises every output block — unvisited VMEM would be garbage.
    """
    idx = np.asarray(idx)
    n_edges = int(idx.shape[0])
    num_blocks = max(1, -(-num_segments // block))
    order = np.argsort(idx, kind="stable").astype(np.int64)
    seg_sorted = idx[order]
    blk_sorted = seg_sorted // block
    counts = np.bincount(blk_sorted, minlength=num_blocks)
    tiles_per_block = np.maximum(1, -(-counts // tile))
    n_tiles = int(tiles_per_block.sum())
    n_slots = n_tiles * tile

    # Vectorised slot construction (no per-block Python loop): block b's
    # slots start at tile * cumsum(tiles_per_block)[b-1]; sorted edge i
    # lands at its block's slot base + its rank within the block.
    slot_base = np.zeros(num_blocks, np.int64)
    np.cumsum(tiles_per_block[:-1] * tile, out=slot_base[1:])
    first_pos = np.cumsum(counts) - counts  # first sorted-edge per block
    slot_of_edge = slot_base[blk_sorted] + (
        np.arange(n_edges, dtype=np.int64) - first_pos[blk_sorted])

    tile_block = np.repeat(
        np.arange(num_blocks, dtype=np.int32), tiles_per_block)
    tile_first = np.ones(n_tiles, np.int32)
    tile_first[1:] = tile_block[1:] != tile_block[:-1]

    # Padding slots carry their block's running-max real segment (block
    # base for empty blocks) and, arbitrarily, source edge 0 — their data
    # is masked out.  Running-max (not block base) keeps the whole slot
    # seg stream non-decreasing: real ids sort ascending within a block,
    # padding sits at their max, and the next block starts strictly
    # higher — so every `indices_are_sorted=True` scatter over this
    # stream (reduce_fallback, the Hessian build, the SCHUR_DIAG
    # preconditioner) rests on a true promise.
    blk_fill = np.arange(num_blocks, dtype=np.int64) * block
    has = counts > 0
    last = np.cumsum(counts) - 1
    blk_fill[has] = seg_sorted[last[has]]
    perm = np.zeros(n_slots, np.int32)
    seg = np.repeat(blk_fill[tile_block], tile)
    mask = np.zeros(n_slots, np.float32)
    perm[slot_of_edge] = order
    seg[slot_of_edge] = seg_sorted
    mask[slot_of_edge] = 1.0
    local = seg - np.repeat(tile_block, tile).astype(np.int64) * block
    tile_lo, window = tile_windows(local.reshape(n_tiles, tile), block)
    return TilePlan(
        tile=tile,
        block=block,
        num_segments=num_segments,
        num_blocks=num_blocks,
        n_edges=n_edges,
        perm=perm,
        seg=seg.astype(np.int32),
        local=local.astype(np.int32),
        mask=mask,
        tile_block=tile_block,
        tile_first=tile_first,
        tile_lo=tile_lo,
        window=window,
    )


def tile_windows(local_by_tile: np.ndarray, block: int,
                 window: int = 0) -> Tuple[np.ndarray, int]:
    """Each tile's one-hot window within its block: (tile_lo, window).

    A tile's slots (padding included) span few consecutive local ids
    when its segments have several edges each — 206 at venice's points,
    of a 2,048-wide block.  `tile_lo` is a tile's smallest local id
    rounded down to a lane multiple (128), `window` the narrowest lane
    multiple that covers every tile from its `tile_lo` (at least the
    `window` given), and `tile_lo` is clamped so that the window ends
    inside the block.  A block that is not a lane multiple, or tiles
    that need the whole block, keep `window == block` and `tile_lo` 0:
    the kernels then build the whole block's one-hot.
    """
    n_tiles = local_by_tile.shape[0]
    lo = local_by_tile.min(axis=1) // LANES * LANES
    need = int((local_by_tile.max(axis=1) - lo).max(initial=0)) + 1
    window = max(window, -(-need // LANES) * LANES)
    if block % LANES or window >= block:
        return np.zeros(n_tiles, np.int32), block
    return np.minimum(lo, block - window).astype(np.int32), window


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """The operand half of a TilePlan (static ints + index arrays).

    Registered as a pytree so it can ride through jit closures and
    lax.while_loop carries untouched (all leaves are constants).  Built
    with host numpy leaves; the program that takes it places them
    (`cached_dual_plans` puts a single-device plan on the device once).
    """

    tile: int
    block: int
    num_segments: int
    num_blocks: int
    window: int  # one-hot width (== block: the whole block's one-hot)
    local: jax.Array  # [1, n_slots] int32 (2-D for Mosaic block specs)
    tile_block: jax.Array  # [n_tiles] int32
    tile_first: jax.Array  # [n_tiles] int32
    tile_lo: jax.Array  # [n_tiles] int32 window start (TilePlan.tile_lo)
    mask: jax.Array  # [n_slots] f32
    perm: jax.Array  # [n_slots] int32
    inv: Optional[jax.Array]  # [n_other] int32: slot in THIS plan holding
    # the other-order slot's edge (cross-order permute), or None


def device_plan(plan: TilePlan, inv: Optional[np.ndarray] = None) -> DevicePlan:
    """The plan's index arrays as a DevicePlan operand (host numpy)."""
    return DevicePlan(
        tile=plan.tile,
        block=plan.block,
        num_segments=plan.num_segments,
        num_blocks=plan.num_blocks,
        window=plan.window,
        local=np.asarray(plan.local)[None, :],
        tile_block=np.asarray(plan.tile_block),
        tile_first=np.asarray(plan.tile_first),
        tile_lo=np.asarray(plan.tile_lo),
        mask=np.asarray(plan.mask),
        perm=np.asarray(plan.perm),
        inv=None if inv is None else np.asarray(inv),
    )


jax.tree_util.register_dataclass(
    DevicePlan,
    data_fields=["local", "tile_block", "tile_first", "tile_lo", "mask",
                 "perm", "inv"],
    meta_fields=["tile", "block", "num_segments", "num_blocks", "window"],
)


def cross_perm(primary: TilePlan, secondary: TilePlan) -> np.ndarray:
    """inv[s_primary] = slot in `secondary` holding the same edge.

    Lets `x_primary = gather(x_secondary, inv)` re-order per-edge rows
    between the two plans.  Padding slots of `primary` point at slot 0
    of `secondary` (their values are masked anyway).
    """
    slot_of_edge = np.zeros(secondary.n_edges, np.int64)
    real = secondary.mask > 0
    slot_of_edge[secondary.perm[real]] = np.nonzero(real)[0]
    inv = slot_of_edge[primary.perm]
    inv[primary.mask == 0] = 0
    return inv.astype(np.int32)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
#
# Every kernel takes a `name`, which its pallas_call gives the compiled
# custom call: the profile's op event is `<name>.<n>` and the op-name
# path ends `<name>/pallas_call`, under the caller's layer scope.  Call
# sites name the kernel and its direction (`coupling_expand_cam_to_edge`,
# `coupling_reduce_edge_to_pt`, `jtj_grad_reduce_cam`, ...), so one
# kernel can be followed across program edits, which renumber the HLO.


def _tile_onehot(local_ref, lo_ref, window):
    """(one-hot [window, T] of this tile's local ids counted from its
    window start, that start): the start is the tile's `tile_lo`, a lane
    multiple as the plan makes it (0 where the window is the block)."""
    lo = pl.multiple_of(lo_ref[pl.program_id(0)], LANES)
    onehot = (
        local_ref[:, :] - lo == jax.lax.broadcasted_iota(
            jnp.int32, (window, local_ref.shape[1]), 0)
    ).astype(jnp.float32)
    return onehot, lo


def _accumulate(out_ref, partial, tf_ref, lo):
    """Add one tile's [F, window] partial sums to its block's resident
    [F, block] output at lane offset `lo`; the block's first tile zeroes
    the block first."""
    @pl.when(tf_ref[pl.program_id(0)] == 1)
    def _zero():
        out_ref[:, :] = jnp.zeros(out_ref.shape, out_ref.dtype)

    win = pl.ds(lo, partial.shape[1])
    out_ref[:, win] = (out_ref[:, win] + partial).astype(out_ref.dtype)


def _reduce_kernel(tb_ref, tf_ref, lo_ref, local_ref, data_ref, out_ref, *,
                   window):
    """Accumulate one tile's [F, T] rows into its block's [F, B] sums."""
    onehot, lo = _tile_onehot(local_ref, lo_ref, window)
    partial = jax.lax.dot_general(
        data_ref[:, :].astype(jnp.float32), onehot,
        (((1,), (1,)), ((), ())),
        precision=fused_ops.ONEHOT_PRECISION,
        preferred_element_type=jnp.float32,
    )  # [F, window]
    _accumulate(out_ref, partial, tf_ref, lo)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "block", "num_blocks", "window", "interpret",
                     "name"))
def _tile_reduce_call(
    data, local, tile_block, tile_first, tile_lo, *, tile, block, num_blocks,
    window, interpret, name="tile_reduce",
):
    F = data.shape[0]
    n_tiles = tile_block.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_block, tile_first, tile_lo
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, tb, tf, lo: (0, i)),
            pl.BlockSpec((F, tile), lambda i, tb, tf, lo: (0, i)),
        ],
        out_specs=pl.BlockSpec(
            (F, block), lambda i, tb, tf, lo: (0, tb[i])),
    )
    return pl.pallas_call(
        functools.partial(_reduce_kernel, window=window),
        grid_spec=grid_spec,
        out_shape=fused_ops.kernel_out_shape(
            (F, num_blocks * block), jnp.float32, local, data),
        interpret=interpret,
        name=name,
    )(tile_block, tile_first, tile_lo, local, data)


def tile_reduce(
    data: jax.Array, plan: DevicePlan, interpret: bool = False,
    name: str = "tile_reduce",
) -> jax.Array:
    """Sum plan-ordered [F, n_slots] rows into [F, num_segments].

    Equivalent (up to f32 summation order) to
    `zeros.at[:, seg].add(data * mask)`; `data` must already be in plan
    slot order with padding slots zero (use `mask_rows` after a gather
    if unsure).
    """
    out = _tile_reduce_call(
        data, plan.local, plan.tile_block, plan.tile_first, plan.tile_lo,
        tile=plan.tile, block=plan.block, num_blocks=plan.num_blocks,
        window=plan.window, interpret=interpret, name=name,
    )
    return out[:, : plan.num_segments].astype(data.dtype)


def _expand_kernel(tb_ref, lo_ref, local_ref, table_ref, out_ref, *, window):
    onehot, lo = _tile_onehot(local_ref, lo_ref, window)
    out_ref[:, :] = jax.lax.dot_general(
        table_ref[:, pl.ds(lo, window)].astype(jnp.float32), onehot,
        (((1,), (0,)), ((), ())),
        precision=fused_ops.ONEHOT_PRECISION,
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "block", "num_blocks", "window", "interpret",
                     "name"))
def _tile_expand_call(
    table, local, tile_block, tile_lo, *, tile, block, num_blocks, window,
    interpret, name="tile_expand",
):
    F = table.shape[0]
    n_tiles = tile_block.shape[0]
    pad = num_blocks * block - table.shape[1]
    table_p = jnp.pad(table, ((0, 0), (0, pad))) if pad else table
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tile_block, tile_lo
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, tb, lo: (0, i)),
            pl.BlockSpec((F, block), lambda i, tb, lo: (0, tb[i])),
        ],
        out_specs=pl.BlockSpec((F, tile), lambda i, tb, lo: (0, i)),
    )
    return pl.pallas_call(
        functools.partial(_expand_kernel, window=window),
        grid_spec=grid_spec,
        out_shape=fused_ops.kernel_out_shape(
            (F, n_tiles * tile), table.dtype, local, table_p),
        interpret=interpret,
        name=name,
    )(tile_block, tile_lo, local, table_p)


def tile_expand(
    table: jax.Array, plan: DevicePlan, interpret: bool = False,
    name: str = "tile_expand",
) -> jax.Array:
    """Gather segment rows to plan-ordered edges: [F, nS] -> [F, n_slots].

    Equivalent to `jnp.take(table, seg, axis=1)` (padding slots read
    their block's running-max real segment; mask before reducing).
    """
    return _tile_expand_call(
        table, plan.local, plan.tile_block, plan.tile_lo,
        tile=plan.tile, block=plan.block, num_blocks=plan.num_blocks,
        window=plan.window, interpret=interpret, name=name,
    )


# ---------------------------------------------------------------------------
# Pure-XLA fallbacks (CPU tests, sharded mesh path)
# ---------------------------------------------------------------------------


def reduce_fallback(data: jax.Array, plan: DevicePlan) -> jax.Array:
    out = jnp.zeros((data.shape[0], plan.num_segments), data.dtype)
    seg = plan.local + plan.tile_block.repeat(plan.tile) * plan.block
    # The sorted promise is honest: build_tile_plan fills padding slots
    # with each block's running-max real segment, so `seg` is globally
    # non-decreasing (junk-block tiles appended by _pad_device_plan sit
    # past num_segments and are dropped).
    return out.at[:, seg[0]].add(
        data, indices_are_sorted=True, mode="drop")


def expand_fallback(table: jax.Array, plan: DevicePlan) -> jax.Array:
    seg = plan.local + plan.tile_block.repeat(plan.tile) * plan.block
    return jnp.take(table, seg[0], axis=1, mode="clip")


def seg_reduce(
    data: jax.Array, plan: DevicePlan, use_kernels: bool,
    name: str = "tile_reduce",
) -> jax.Array:
    """Plan-ordered rows -> per-segment sums; kernel or XLA fallback."""
    if use_kernels:
        return tile_reduce(data, plan, name=name)
    return reduce_fallback(data, plan)


def seg_expand(
    table: jax.Array, plan: DevicePlan, use_kernels: bool,
    name: str = "tile_expand",
) -> jax.Array:
    """Per-segment rows -> plan-ordered per-edge rows (gather)."""
    if use_kernels:
        return tile_expand(table, plan, name=name)
    return expand_fallback(table, plan)


# ---------------------------------------------------------------------------
# Fused J^T J + gradient build (the makeHSchur / makeHppHllSchur analog)
# ---------------------------------------------------------------------------


def _jtj_kernel(tb_ref, tf_ref, lo_ref, local_ref, j_ref, r_ref, out_ref,
                *, window, d, od):
    """One tile: rows of J^T J (d*d) and -J^T r (d) reduced to its block.

    The per-edge outer-product rows are built in VMEM from the [od*d, T]
    Jacobian block and immediately contracted onto the block axis with
    one MXU matmul — the feature rows never touch HBM (the reference
    fuses the same way with shared-memory staging + atomicAdd,
    build_linear_system.cu:88-146).
    """
    rows = []
    for a in range(d):
        for b in range(d):
            acc = None
            for o in range(od):
                t = j_ref[o * d + a, :] * j_ref[o * d + b, :]
                acc = t if acc is None else acc + t
            rows.append(acc[None, :])
    for a in range(d):
        acc = None
        for o in range(od):
            t = j_ref[o * d + a, :] * r_ref[o, :]
            acc = t if acc is None else acc + t
        rows.append(-acc[None, :])
    feat = jnp.concatenate(rows, axis=0).astype(jnp.float32)  # [d*d+d, T]
    onehot, lo = _tile_onehot(local_ref, lo_ref, window)
    partial = jax.lax.dot_general(
        feat, onehot, (((1,), (1,)), ((), ())),
        precision=fused_ops.ONEHOT_PRECISION,
        preferred_element_type=jnp.float32,
    )  # [d*d+d, window]
    _accumulate(out_ref, partial, tf_ref, lo)


@functools.partial(
    jax.jit,
    static_argnames=("d", "od", "tile", "block", "num_blocks", "window",
                     "interpret", "name"))
def _jtj_reduce_call(
    J, r, local, tile_block, tile_first, tile_lo, *, d, od, tile, block,
    num_blocks, window, interpret, name="jtj_grad_reduce",
):
    n_tiles = tile_block.shape[0]
    feat = d * d + d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_block, tile_first, tile_lo
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, tb, tf, lo: (0, i)),
            pl.BlockSpec((od * d, tile), lambda i, tb, tf, lo: (0, i)),
            pl.BlockSpec((od, tile), lambda i, tb, tf, lo: (0, i)),
        ],
        out_specs=pl.BlockSpec(
            (feat, block), lambda i, tb, tf, lo: (0, tb[i])),
    )
    return pl.pallas_call(
        functools.partial(_jtj_kernel, window=window, d=d, od=od),
        grid_spec=grid_spec,
        out_shape=fused_ops.kernel_out_shape(
            (feat, num_blocks * block), jnp.float32, local, J, r),
        interpret=interpret,
        name=name,
    )(tile_block, tile_first, tile_lo, local, J, r)


def jtj_grad_reduce(
    J: jax.Array,
    r: jax.Array,
    plan: DevicePlan,
    use_kernels: bool,
    interpret: bool = False,
    name: str = "jtj_grad_reduce",
) -> Tuple[jax.Array, jax.Array]:
    """Fused block-diagonal Hessian + gradient for one vertex kind.

    J [od*d, n_slots], r [od, n_slots] in plan slot order (weighted and
    masked).  Returns (h_rows [d*d, nS], g_rows [d, nS]) — the rows of
    sum_e J_e^T J_e and -J_e^T r_e per segment.
    """
    od = r.shape[0]
    d = J.shape[0] // od
    if use_kernels or interpret:
        out = _jtj_reduce_call(
            J, r, plan.local, plan.tile_block, plan.tile_first, plan.tile_lo,
            d=d, od=od, tile=plan.tile, block=plan.block,
            num_blocks=plan.num_blocks, window=plan.window,
            interpret=interpret, name=name)
        out = out[:, : plan.num_segments].astype(J.dtype)
    else:
        out = _jtj_fallback_chunked(J, r, plan, d, od)
    return out[: d * d], out[d * d:]


def _jtj_fallback_chunked(J, r, plan: DevicePlan, d: int, od: int,
                          chunk: int = 65_536) -> jax.Array:
    """XLA fallback of the fused build, chunked over slots.

    This is the degradation route when Mosaic rejects the kernels on a
    real TPU (probe_kernels False) and the CPU test path — so its
    transient memory must stay bounded: at Final scale the un-chunked
    [d*d+d, n_slots] feature-row matrix is ~10 GB.  Slot chunks keep it
    to [d*d+d, chunk] (~23 MB at the default), and slices of the
    plan-sorted seg stream stay non-decreasing, so the scatter keeps its
    sorted promise.
    """
    feat = d * d + d
    seg = (plan.local
           + plan.tile_block.repeat(plan.tile)[None, :] * plan.block)[0]
    n = seg.shape[0]
    # Derive the accumulator from J so that inside shard_map its
    # varying-axes type matches the loop body's output (J/r/seg are
    # device-varying; a plain jnp.zeros carry is replicated-typed and
    # lax.fori_loop rejects the carry-type mismatch).  isnan keeps the
    # seed finite-zero even when J[0, 0] is inf/NaN (J * 0 would
    # broadcast NaN into every accumulator cell) while still making the
    # value data-dependent for the varying-axes tracer.
    seed = jnp.isnan(J[0, 0]).astype(J.dtype) * 0
    out = jnp.zeros((feat, plan.num_segments), J.dtype) + seed

    def rows_of(Jc, rc):
        return jnp.concatenate([
            jnp.stack([
                sum(Jc[o * d + a] * Jc[o * d + b] for o in range(od))
                for a in range(d) for b in range(d)]),
            jnp.stack([
                -sum(Jc[o * d + a] * rc[o] for o in range(od))
                for a in range(d)]),
        ])

    if n <= chunk:
        return out.at[:, seg].add(
            rows_of(J, r), indices_are_sorted=True, mode="drop")

    # Pad to a whole number of chunks with inert slots (zero data,
    # out-of-range segment -> dropped by the scatter) so every fori_loop
    # step slices a full static-size chunk — no clamped dynamic_slice
    # overlap double-counting the tail.
    pad = (-n) % chunk
    if pad:
        J = jnp.pad(J, ((0, 0), (0, pad)))
        r = jnp.pad(r, ((0, 0), (0, pad)))
        # Pad with num_blocks*block, not num_segments: sharded plans
        # padded by _pad_device_plan carry junk-block slots with seg up
        # to num_blocks*block - 1 >= num_segments, so only this value is
        # guaranteed >= every live or junk seg — keeping the padded tail
        # non-decreasing as indices_are_sorted=True promises.  Still
        # out of range, so the scatter drops it.
        seg = jnp.pad(seg, (0, pad),
                      constant_values=plan.num_blocks * plan.block)

    def body(k, acc):
        start = k * chunk
        Jc = jax.lax.dynamic_slice_in_dim(J, start, chunk, axis=1)
        rc = jax.lax.dynamic_slice_in_dim(r, start, chunk, axis=1)
        sc = jax.lax.dynamic_slice_in_dim(seg, start, chunk)
        return acc.at[:, sc].add(
            rows_of(Jc, rc), indices_are_sorted=True, mode="drop")

    return jax.lax.fori_loop(0, seg.shape[0] // chunk, body, out)


# ---------------------------------------------------------------------------
# Fused coupling-product kernels: (expand -> J.x) and (J^T.u -> reduce)
# ---------------------------------------------------------------------------


def _expand_matvec_kernel(tb_ref, lo_ref, local_ref, j_ref, table_ref,
                          out_ref, *, window, d):
    """u[o] = sum_a J[o*d+a] * table[a, seg]: gather + per-edge matvec.

    The vertex table block lives entirely in VMEM; the gather is the
    one-hot matmul, the [od, T] product rows are the only HBM write —
    the [d, T] expanded rows never exist outside VMEM.
    """
    od = j_ref.shape[0] // d
    onehot, lo = _tile_onehot(local_ref, lo_ref, window)
    pe = jax.lax.dot_general(
        table_ref[:, pl.ds(lo, window)].astype(jnp.float32), onehot,
        (((1,), (0,)), ((), ())),
        precision=fused_ops.ONEHOT_PRECISION,
        preferred_element_type=jnp.float32,
    )  # [d, T]
    for o in range(od):
        acc = None
        for a in range(d):
            t = j_ref[o * d + a, :].astype(jnp.float32) * pe[a, :]
            acc = t if acc is None else acc + t
        out_ref[o, :] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("d", "tile", "block", "num_blocks", "window",
                     "interpret", "name"))
def _expand_matvec_call(
    J, table, local, tile_block, tile_lo, *, d, tile, block, num_blocks,
    window, interpret, name="coupling_expand",
):
    od = J.shape[0] // d
    n_tiles = tile_block.shape[0]
    pad = num_blocks * block - table.shape[1]
    table_p = jnp.pad(table, ((0, 0), (0, pad))) if pad else table
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tile_block, tile_lo
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, tb, lo: (0, i)),
            pl.BlockSpec((J.shape[0], tile), lambda i, tb, lo: (0, i)),
            pl.BlockSpec((d, block), lambda i, tb, lo: (0, tb[i])),
        ],
        out_specs=pl.BlockSpec((od, tile), lambda i, tb, lo: (0, i)),
    )
    return pl.pallas_call(
        functools.partial(_expand_matvec_kernel, window=window, d=d),
        grid_spec=grid_spec,
        out_shape=fused_ops.kernel_out_shape(
            (od, n_tiles * tile), jnp.float32, local, J, table_p),
        interpret=interpret,
        name=name,
    )(tile_block, tile_lo, local, J, table_p)


def _matvec_reduce_kernel(tb_ref, tf_ref, lo_ref, local_ref, j_ref, u_ref,
                          out_ref, *, window, d):
    """out[b, seg] += sum_o J[o*d+b] * u[o]: per-edge J^T u + reduce.

    The [d, T] product rows are formed in VMEM and immediately
    contracted onto the block axis — they never touch HBM.
    """
    od = u_ref.shape[0]
    rows = []
    for b in range(d):
        acc = None
        for o in range(od):
            t = (j_ref[o * d + b, :].astype(jnp.float32)
                 * u_ref[o, :].astype(jnp.float32))
            acc = t if acc is None else acc + t
        rows.append(acc[None, :])
    te = jnp.concatenate(rows, axis=0)  # [d, T]
    onehot, lo = _tile_onehot(local_ref, lo_ref, window)
    partial = jax.lax.dot_general(
        te, onehot, (((1,), (1,)), ((), ())),
        precision=fused_ops.ONEHOT_PRECISION,
        preferred_element_type=jnp.float32,
    )  # [d, window]
    _accumulate(out_ref, partial, tf_ref, lo)


@functools.partial(
    jax.jit,
    static_argnames=("d", "tile", "block", "num_blocks", "window",
                     "interpret", "name"))
def _matvec_reduce_call(
    J, u, local, tile_block, tile_first, tile_lo, *, d, tile, block,
    num_blocks, window, interpret, name="coupling_reduce",
):
    n_tiles = tile_block.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_block, tile_first, tile_lo
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, tb, tf, lo: (0, i)),
            pl.BlockSpec((J.shape[0], tile), lambda i, tb, tf, lo: (0, i)),
            pl.BlockSpec((u.shape[0], tile), lambda i, tb, tf, lo: (0, i)),
        ],
        out_specs=pl.BlockSpec(
            (d, block), lambda i, tb, tf, lo: (0, tb[i])),
    )
    return pl.pallas_call(
        functools.partial(_matvec_reduce_kernel, window=window, d=d),
        grid_spec=grid_spec,
        out_shape=fused_ops.kernel_out_shape(
            (d, num_blocks * block), jnp.float32, local, J, u),
        interpret=interpret,
        name=name,
    )(tile_block, tile_first, tile_lo, local, J, u)


def coupling_expand(
    table: jax.Array,
    J: jax.Array,
    plan: DevicePlan,
    d: int,
    use_kernels: bool,
    interpret: bool = False,
    name: str = "coupling_expand",
) -> jax.Array:
    """u[o] = sum_a J[o*d+a] * table[a, seg]  -> [od, n_slots] rows.

    The fused (gather + J.x) half of a coupling product: J in plan slot
    order, table [d, num_segments].  Output is float32.
    """
    if use_kernels or interpret:
        return _expand_matvec_call(
            J, table.astype(jnp.float32), plan.local, plan.tile_block,
            plan.tile_lo, d=d, tile=plan.tile, block=plan.block,
            num_blocks=plan.num_blocks, window=plan.window,
            interpret=interpret, name=name)
    od = J.shape[0] // d
    pe = expand_fallback(table, plan)
    return jnp.stack([
        sum(J[o * d + a].astype(jnp.float32) * pe[a] for a in range(d))
        for o in range(od)
    ])


def coupling_reduce(
    J: jax.Array,
    u: jax.Array,
    plan: DevicePlan,
    d: int,
    use_kernels: bool,
    interpret: bool = False,
    name: str = "coupling_reduce",
) -> jax.Array:
    """out[b, seg] = sum_edges sum_o J[o*d+b] * u[o]  -> [d, nS].

    The fused (J^T.u + segment reduce) half of a coupling product.
    """
    if use_kernels or interpret:
        out = _matvec_reduce_call(
            J, u, plan.local, plan.tile_block, plan.tile_first, plan.tile_lo,
            d=d, tile=plan.tile, block=plan.block,
            num_blocks=plan.num_blocks, window=plan.window,
            interpret=interpret, name=name)
        return out[:, : plan.num_segments]
    od = u.shape[0]
    te = jnp.stack([
        sum(J[o * d + b].astype(jnp.float32) * u[o] for o in range(od))
        for b in range(d)
    ])
    return reduce_fallback(te, plan)


# ---------------------------------------------------------------------------
# Dual plans: camera-sorted primary order + point-sorted secondary order
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DualPlans:
    """Both edge orderings of one BA problem + cross permutations.

    The primary (slot) order of all edge arrays is `cam`'s slot order;
    `pt.inv[s_pt]` is the cam slot holding pt-slot s_pt's edge, and
    `cam.inv[s_cam]` the reverse.  `use_kernels` selects the Pallas
    kernels (real TPU) vs the XLA fallback (CPU tests, interpret-free).

    `fused_to_pt`/`fused_to_cam` are the OPTIONAL bucket-structured
    plans of the fused edge-pipeline kernels (ops/fused.py), expressed
    over the SAME cam-slot edge stream; None (the default) keeps the
    pytree — and every lowered program — byte-identical to the
    pre-fused layout, so attaching them only under
    `SolverOption(fused_kernels=True)` is the dark-landing guarantee.
    """

    cam: DevicePlan
    pt: DevicePlan
    use_kernels: bool
    fused_to_pt: Optional["fused_ops.DeviceFusedPlan"] = None
    fused_to_cam: Optional["fused_ops.DeviceFusedPlan"] = None

    # -- conversions between the two slot orders (per-edge rows) --
    def to_pt(self, rows_cam: jax.Array) -> jax.Array:
        return jnp.take(
            rows_cam, self.pt.inv, axis=1, mode="clip") * self.pt.mask

    def to_cam(self, rows_pt: jax.Array) -> jax.Array:
        return jnp.take(
            rows_pt, self.cam.inv, axis=1, mode="clip") * self.cam.mask


jax.tree_util.register_dataclass(
    DualPlans,
    data_fields=["cam", "pt", "fused_to_pt", "fused_to_cam"],
    meta_fields=["use_kernels"])


def make_dual_plans(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    tile_cam: int = DEFAULT_TILE_CAM,
    block_cam: int = DEFAULT_BLOCK_CAM,
    tile_pt: int = DEFAULT_TILE_PT,
    block_pt: int = DEFAULT_BLOCK_PT,
    use_kernels: Optional[bool] = None,
    fit: bool = True,
) -> Tuple[TilePlan, DualPlans]:
    """Plan both orderings.  Returns (cam_host_plan, DualPlans with host
    numpy leaves).

    The caller must reorder every edge array into the cam plan's slot
    order (`arr[:, cam_plan.perm] * cam_plan.mask`) — that order is the
    canonical edge axis from here on.  The pt plan is expressed in
    cam-slot space, so `pt.inv` indexes cam slots directly.

    `fit=False` uses `tile_cam`/`tile_pt` verbatim — the sharded planner
    fits them ONCE from the largest shard so every shard's plan leaves
    share one tile shape and stack cleanly.
    """
    cam_idx = np.asarray(cam_idx)
    pt_idx = np.asarray(pt_idx)
    if fit:
        # Keep tiles from dwarfing tiny problems (tests, toy datasets).
        n = cam_idx.shape[0]
        tile_cam = _fit_tile(tile_cam, n)
        tile_pt = _fit_tile(tile_pt, n)

    plan_c = build_tile_plan(cam_idx, num_cameras, tile_cam, block_cam)
    # The pt plan is built over the CAM-SLOT edge stream: segment id of a
    # cam slot is its edge's point (padding slots get an out-of-range
    # marker sorted to the end and masked).
    pt_of_slot = np.where(
        plan_c.mask > 0, pt_idx[plan_c.perm], num_points)
    plan_p_raw = build_tile_plan(
        np.minimum(pt_of_slot, num_points - 1).astype(np.int64),
        num_points, tile_pt, block_pt)
    # Mask out slots whose source cam slot was itself padding.
    src_mask = (plan_c.mask > 0)[plan_p_raw.perm]
    mask_p = plan_p_raw.mask * src_mask
    plan_p = dataclasses.replace(plan_p_raw, mask=mask_p.astype(np.float32))

    inv_pt = plan_p.perm.astype(np.int32)  # pt slot -> cam slot
    inv_pt = np.where(plan_p.mask > 0, inv_pt, 0).astype(np.int32)
    # cam slot -> pt slot
    slot_of_cam = np.zeros(plan_c.n_slots, np.int64)
    real_p = plan_p.mask > 0
    slot_of_cam[plan_p.perm[real_p]] = np.nonzero(real_p)[0]
    inv_cam = np.where(
        plan_c.mask > 0, slot_of_cam[np.arange(plan_c.n_slots)], 0
    ).astype(np.int32)

    if use_kernels is None:
        use_kernels = probe_kernels()
    dp_c = device_plan(plan_c, inv_cam)
    dp_p = device_plan(plan_p, inv_pt)
    return plan_c, DualPlans(cam=dp_c, pt=dp_p, use_kernels=use_kernels)


def _pad_device_plan(dp: DevicePlan, n_tiles_to: int, junk_block: bool):
    """Append inert tiles so stacked shards share one tile count (host
    numpy leaves in and out).

    Padding tiles target a dedicated JUNK block appended after the real
    ones (first=1 on the first padding tile) — pointing them at a real
    block would revisit it non-consecutively, which the sequential-
    accumulation kernels do not support.
    """
    n_tiles = dp.tile_block.shape[0]
    add = n_tiles_to - n_tiles
    nb = dp.num_blocks + (1 if junk_block else 0)
    if add == 0 and not junk_block:
        return dp
    if add:
        pad = add * dp.tile
        local = np.concatenate(
            [dp.local, np.zeros((1, pad), np.int32)], axis=1)
        tb = np.concatenate(
            [dp.tile_block, np.full((add,), nb - 1, np.int32)])
        tf = np.concatenate(
            [dp.tile_first, np.asarray([1] + [0] * (add - 1), np.int32)])
        lo = np.concatenate([dp.tile_lo, np.zeros((add,), np.int32)])
        mask = np.concatenate([dp.mask, np.zeros((pad,), dp.mask.dtype)])
        perm = np.concatenate([dp.perm, np.zeros((pad,), np.int32)])
        inv = dp.inv
        if inv is not None:
            inv = np.concatenate([inv, np.zeros((pad,), np.int32)])
    else:
        local, tb, tf, lo, mask, perm, inv = (
            dp.local, dp.tile_block, dp.tile_first, dp.tile_lo, dp.mask,
            dp.perm, dp.inv)
    return dataclasses.replace(
        dp, num_blocks=nb, local=local, tile_block=tb, tile_first=tf,
        tile_lo=lo, mask=mask, perm=perm, inv=inv)


def _widen_window(dp: DevicePlan, window: int) -> DevicePlan:
    """`dp` with one-hot windows at least `window` wide (host numpy):
    stacked shards share one static window, the widest shard's."""
    lo, window = tile_windows(
        np.asarray(dp.local).reshape(-1, dp.tile), dp.block, window)
    return dataclasses.replace(dp, tile_lo=lo, window=window)


def make_sharded_dual_plans(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    world_size: int,
    tile_cam: int = DEFAULT_TILE_CAM,
    block_cam: int = DEFAULT_BLOCK_CAM,
    tile_pt: int = DEFAULT_TILE_PT,
    block_pt: int = DEFAULT_BLOCK_PT,
    use_kernels: Optional[bool] = None,
):
    """Per-shard dual plans for the edge-sharded mesh path.

    Edges are camera-sorted and split into `world_size` contiguous
    chunks (the reference's contiguous partition, memory_pool.h:48-63);
    each shard gets its own dual plans over its local edges — so every
    reduction, expansion, and cross permute stays shard-local, and the
    psums in builder/pcg combine the full-size per-shard outputs exactly
    as in the fallback path.  The plan leaves stay host numpy: the
    sharded program places each shard's slice on its own device, and
    nothing is built on (or routed through) the first device.

    Returns (perm [ws, slots_c], mask [ws, slots_c], cam_seg
    [ws, slots_c], stacked DualPlans whose leaves carry a leading shard
    axis): shard k's edge arrays are `arr[perm[k]] * mask[k]`, and
    `cam_seg[k]` is the camera id per slot — non-decreasing within the
    shard (padding carries each block's running-max camera; junk-block
    slots are clipped to num_cameras-1), so it can be used directly as a
    sorted `cam_idx` stream.  Every per-shard plan covers ALL global
    segments (so outputs align for the psum); both plan kinds are padded
    to the max per-shard tile count with junk-block tiles, and tile
    sizes are fitted ONCE from the largest shard so every shard's plan
    leaves share one tile shape (stacking would fail otherwise); each
    plan kind takes its widest shard's one-hot window, a static shape
    too.
    """
    cam_idx = np.asarray(cam_idx)
    pt_idx = np.asarray(pt_idx)
    n = cam_idx.shape[0]
    order = np.argsort(cam_idx, kind="stable")
    bounds = [(k * n) // world_size for k in range(world_size + 1)]
    n_max = max(bounds[k + 1] - bounds[k] for k in range(world_size))
    tile_cam = _fit_tile(tile_cam, n_max)
    tile_pt = _fit_tile(tile_pt, n_max)

    plans = []
    for k in range(world_size):
        sel = order[bounds[k]: bounds[k + 1]]
        _, dp = make_dual_plans(
            cam_idx[sel], pt_idx[sel], num_cameras, num_points,
            tile_cam, block_cam, tile_pt, block_pt, use_kernels,
            fit=False)
        # Re-express perms in global edge ids.
        cam_perm = sel.astype(np.int64)[dp.cam.perm]
        plans.append((dp, cam_perm))

    max_tc = max(int(dp.cam.tile_block.shape[0]) for dp, _ in plans)
    max_tp = max(int(dp.pt.tile_block.shape[0]) for dp, _ in plans)
    max_wc = max(dp.cam.window for dp, _ in plans)
    max_wp = max(dp.pt.window for dp, _ in plans)
    stacked_c, stacked_p, perms = [], [], []
    for dp, cam_perm in plans:
        slots_before = int(dp.cam.mask.shape[0])
        c = _pad_device_plan(
            _widen_window(dp.cam, max_wc), max_tc, junk_block=True)
        p = _pad_device_plan(
            _widen_window(dp.pt, max_wp), max_tp, junk_block=True)
        pad_slots = int(c.mask.shape[0]) - slots_before
        if pad_slots:
            cam_perm = np.concatenate(
                [cam_perm, np.zeros(pad_slots, np.int64)])
        stacked_c.append(c)
        stacked_p.append(p)
        perms.append(cam_perm)

    def stack(dps):
        leaves = [jax.tree_util.tree_leaves(d) for d in dps]
        stacked = [np.stack(vals) for vals in zip(*leaves)]
        treedef = jax.tree_util.tree_structure(dps[0])
        return jax.tree_util.tree_unflatten(treedef, stacked)

    dual = DualPlans(
        cam=stack(stacked_c), pt=stack(stacked_p),
        use_kernels=plans[0][0].use_kernels)
    masks = np.stack([np.asarray(c.mask) for c in stacked_c])
    cam_segs = np.stack([
        np.minimum(
            np.asarray(c.local)[0]
            + np.repeat(np.asarray(c.tile_block), c.tile) * c.block,
            num_cameras - 1,
        ).astype(np.int32)
        for c in stacked_c])
    return np.stack(perms), masks, cam_segs, dual


def squeeze_plans(plans: DualPlans) -> DualPlans:
    """Drop the leading shard axis inside a shard_map body."""
    return jax.tree_util.tree_map(lambda x: x[0], plans)


# ---------------------------------------------------------------------------
# Host plan cache
# ---------------------------------------------------------------------------
#
# Plan construction is pure host work (argsorts + bincounts over the
# edge axis) and depends only on the problem GRAPH and the tile
# geometry — not on parameters or observations.  Repeated solves of
# one problem (benchmark windows, chunked/checkpointed drivers, the
# auditor's canonical lowerings, parameter sweeps) therefore reuse one
# plan, keyed by a content fingerprint of the index arrays.  A strong
# digest (blake2b), not Python's hash(): a collision would silently
# solve the wrong graph.

_PLAN_CACHE: "dict" = {}
_PLAN_CACHE_DEFAULT_MAX = 8  # LRU bound: plans pin host+device index arrays
# Monotone eviction counter: a fleet of mixed shape classes churning a
# too-small cache shows up here (solve.flat_solve surfaces the delta as
# a `plan_cache_evict` PhaseTimer event next to `plan_cache_hit`).
_PLAN_CACHE_EVICTIONS = 0


def plan_cache_capacity() -> int:
    """LRU capacity of the host plan cache.

    `MEGBA_PLAN_CACHE=<n>` overrides the default of
    `_PLAN_CACHE_DEFAULT_MAX` (8): a fleet serving many shape classes
    evicts pathologically at 8, while a single-problem pipeline gains
    nothing from more.  Read at insertion time so tests (and long-lived
    services) can retune without reimporting; `<n> >= 1`.
    """
    env = os.environ.get("MEGBA_PLAN_CACHE")
    if env is None:
        return _PLAN_CACHE_DEFAULT_MAX
    try:
        cap = int(env)
    except ValueError as e:
        raise ValueError(
            f"MEGBA_PLAN_CACHE must be an integer >= 1, got {env!r}") from e
    if cap < 1:
        raise ValueError(
            f"MEGBA_PLAN_CACHE must be an integer >= 1, got {env!r}")
    return cap


def plan_cache_evictions() -> int:
    """Total plan-cache evictions this process (monotone counter)."""
    return _PLAN_CACHE_EVICTIONS


def _array_digest(a: np.ndarray) -> bytes:
    import hashlib

    a = np.ascontiguousarray(a)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.digest()


def _plan_cache_get(key):
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        # Refresh LRU position (dicts preserve insertion order).
        _PLAN_CACHE.pop(key)
        _PLAN_CACHE[key] = hit
    return hit


def _plan_cache_put(key, value):
    global _PLAN_CACHE_EVICTIONS
    cap = plan_cache_capacity()
    while len(_PLAN_CACHE) >= cap:
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        _PLAN_CACHE_EVICTIONS += 1
    _PLAN_CACHE[key] = value


def _cached_plan(tag, arrays, params, build, timer=None):
    """`build()` behind the host plan cache, keyed by `tag`, the digest
    of each of `arrays` (None stays None) and `params`.  Returns
    (value, cache_hit); the digest and the lookup are `timer`'s
    `plan.key` sub-phase (utils.timing.PhaseTimer)."""
    with (timer.phase("plan.key") if timer is not None
          else contextlib.nullcontext()):
        key = (tag, *(None if a is None else _array_digest(a)
                      for a in arrays), *params)
        hit = _plan_cache_get(key)
    if hit is not None:
        return hit, True
    value = build()
    _plan_cache_put(key, value)
    return value, False


def cached_dual_plans(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    tile_cam: int = DEFAULT_TILE_CAM,
    block_cam: int = DEFAULT_BLOCK_CAM,
    tile_pt: int = DEFAULT_TILE_PT,
    block_pt: int = DEFAULT_BLOCK_PT,
    use_kernels: Optional[bool] = None,
    timer=None,
):
    """`make_dual_plans` behind the host plan cache, its DualPlans placed
    once on the default device (the single-device solve's).

    Returns ((cam_host_plan, DualPlans), cache_hit).  `use_kernels` is
    resolved (probe_kernels) BEFORE keying, so a plan probed on one
    backend can never serve a solve on another.
    """
    if use_kernels is None:
        use_kernels = probe_kernels()

    def build():
        plan_c, dual = make_dual_plans(
            cam_idx, pt_idx, num_cameras, num_points,
            tile_cam, block_cam, tile_pt, block_pt, use_kernels)
        return plan_c, jax.device_put(dual)

    return _cached_plan(
        "single", (cam_idx, pt_idx),
        (int(num_cameras), int(num_points),
         tile_cam, block_cam, tile_pt, block_pt, use_kernels), build, timer)


def cached_sharded_dual_plans(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    world_size: int,
    tile_cam: int = DEFAULT_TILE_CAM,
    block_cam: int = DEFAULT_BLOCK_CAM,
    tile_pt: int = DEFAULT_TILE_PT,
    block_pt: int = DEFAULT_BLOCK_PT,
    use_kernels: Optional[bool] = None,
    timer=None,
):
    """`make_sharded_dual_plans` behind the host plan cache.

    Returns ((perms, masks, cam_segs, DualPlans), cache_hit)."""
    if use_kernels is None:
        use_kernels = probe_kernels()
    return _cached_plan(
        "sharded", (cam_idx, pt_idx),
        (int(num_cameras), int(num_points), int(world_size),
         tile_cam, block_cam, tile_pt, block_pt, use_kernels),
        lambda: make_sharded_dual_plans(
            cam_idx, pt_idx, num_cameras, num_points, world_size,
            tile_cam, block_cam, tile_pt, block_pt, use_kernels),
        timer)


# ---------------------------------------------------------------------------
# Camera-graph cluster plan (two-level Schur preconditioner coarse space)
# ---------------------------------------------------------------------------
#
# The two-level preconditioner (solver/precond.py) needs three pieces of
# pure GRAPH structure, all host-computable at plan time and cacheable
# behind the same content-fingerprint LRU as the tile plans:
#
#   1. an aggregation of cameras into O(sqrt(Nc)) clusters — greedy,
#      co-observation-weighted (cameras that share many points merge
#      first), so a point's edges concentrate in few clusters;
#   2. the distinct (point, cluster) incidences ("pc-slots"): the
#      coarse-projected coupling R·Hpl has one [cd, pd] block per
#      incidence (V_{p,I} = Σ_{e: pt(e)=p, cluster(cam(e))=I} W_e), and
#      the device build scatter-adds per-edge W rows into them via the
#      per-edge `pc_slot` stream;
#   3. the (edge, pc-slot-of-same-point) incidence pairs ("ec-pairs"):
#      the columns of G = S_d·Rᵀ pick up one W_e·Hll⁻¹·V_sᵀ block per
#      pair — enumerated once here (Σ_e k_{pt(e)} entries, k_p =
#      clusters seeing point p, small under co-observation clustering)
#      so the device side is a plain gather → block product → segment
#      scatter into the [cd·cd, Nc·C] coarse-coupling table.  G is what
#      makes the MULTIPLICATIVE two-level cycle collective-free inside
#      the PCG body: the cycle's S applications only ever hit vectors
#      in range(Rᵀ), which G materialises once per build.
#
# Everything downstream is selects/gathers/scatter-adds over these
# static index arrays.  Sharding story: `pc_slot` and the ec arrays
# follow the edge shards; V and G are each psum-combined once per build
# (OUTSIDE the PCG body — the all-reduce kind the solver already
# emits), and everything after is identical tiny replicated work per
# shard.  The per-apply cycle adds no collectives at all.


@dataclasses.dataclass(frozen=True)
class ClusterPlan:
    """Host half of the camera-cluster coarse-space plan.

    The ec arrays are laid out in `world_size` equal-length contiguous
    shard groups (each padded to the common max with inert entries —
    local edge 0, slot 0, out-of-range segment), and `ec_edge` holds
    SHARD-LOCAL edge indices, so a `P(EDGE_AXIS)` split hands every
    shard exactly the pairs of its own edges.
    """

    num_cameras: int
    num_clusters: int  # actual cluster count C (>= the target)
    n_pc: int  # distinct (point, cluster) incidences
    n_ec: int  # real (unpadded) edge-incidence pairs
    world_size: int
    cluster: np.ndarray  # [Nc] int32 cluster id per camera
    pc_slot: np.ndarray  # [nE] int32 incidence per edge (n_pc = inert)
    pc_pt: np.ndarray  # [n_pc] int32 point of each incidence
    ec_edge: np.ndarray  # [ws*L] int32 shard-LOCAL edge per pair
    ec_slot: np.ndarray  # [ws*L] int32 pc-slot per pair
    ec_seg: np.ndarray  # [ws*L] int32 cam*C+cluster (Nc*C on padding)


@dataclasses.dataclass(frozen=True)
class DeviceClusterPlan:
    """Device half: static ints + index arrays, registered as a pytree
    so it rides jit/shard_map operands like DualPlans does."""

    num_clusters: int
    n_pc: int
    cluster: jax.Array  # [Nc] int32
    pc_slot: jax.Array  # [nE] int32 (edge axis; shard-local when sharded)
    pc_pt: jax.Array  # [n_pc] int32
    ec_edge: jax.Array  # [ws*L] int32 (edge-sharded; local edge ids)
    ec_slot: jax.Array  # [ws*L] int32 (edge-sharded)
    ec_seg: jax.Array  # [ws*L] int32 (edge-sharded)


jax.tree_util.register_dataclass(
    DeviceClusterPlan,
    data_fields=["cluster", "pc_slot", "pc_pt", "ec_edge", "ec_slot",
                 "ec_seg"],
    meta_fields=["num_clusters", "n_pc"],
)


def device_cluster_plan(plan: ClusterPlan) -> DeviceClusterPlan:
    return DeviceClusterPlan(
        num_clusters=plan.num_clusters,
        n_pc=plan.n_pc,
        cluster=jnp.asarray(plan.cluster),
        pc_slot=jnp.asarray(plan.pc_slot),
        pc_pt=jnp.asarray(plan.pc_pt),
        ec_edge=jnp.asarray(plan.ec_edge),
        ec_slot=jnp.asarray(plan.ec_slot),
        ec_seg=jnp.asarray(plan.ec_seg),
    )


def cluster_partition_specs(cplan: DeviceClusterPlan, edge_spec=None):
    """shard_map in_specs tree for a DeviceClusterPlan operand: the
    per-edge `pc_slot` stream and the per-pair ec arrays follow the
    edge shards (the plan builder laid the pairs out in equal-length
    shard groups with shard-local edge ids); the cluster table and
    incidence tables ride replicated (the coarse assembly after the V/G
    psums is identical tiny work per shard).  `edge_spec` overrides the
    edge-following spec — the 2-D mesh passes
    P((EDGE_AXIS, CAM_AXIS)), whose device-block order the plan's
    world_size shard groups must match (parallel/mesh.py lays both out
    edge-major, camera-minor)."""
    from jax.sharding import PartitionSpec as P

    from megba_tpu.parallel.mesh import EDGE_AXIS

    if edge_spec is None:
        edge_spec = P(EDGE_AXIS)
    return DeviceClusterPlan(
        num_clusters=cplan.num_clusters, n_pc=cplan.n_pc,
        cluster=P(), pc_slot=edge_spec, pc_pt=P(),
        ec_edge=edge_spec, ec_slot=edge_spec, ec_seg=edge_spec)


def build_camera_clusters(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    target: int = 0,
    mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Greedy co-observation-weighted aggregation into ~target clusters.

    target = 0 selects the two-level default, ceil(sqrt(Nc)).  Camera
    pairs are weighted by how many points they co-observe (counted over
    consecutive cameras in each point's sorted camera list — Σ(deg_p−1)
    pairs total, so the host cost stays O(nE log nE) at any scale) and
    merged heaviest-first under a size cap of ceil(Nc / target) via
    union-find.  Returns [Nc] int32 cluster ids in [0, C); C >= target
    whenever the cap binds, and every camera (including edge-less ones)
    gets a cluster.
    """
    cam_idx = np.asarray(cam_idx, np.int64)
    pt_idx = np.asarray(pt_idx, np.int64)
    if mask is not None:
        keep = np.asarray(mask) > 0
        cam_idx, pt_idx = cam_idx[keep], pt_idx[keep]
    if target <= 0:
        target = max(1, int(np.ceil(np.sqrt(num_cameras))))
    target = min(target, num_cameras)
    cap = max(1, -(-num_cameras // target))

    parent = np.arange(num_cameras, dtype=np.int64)
    size = np.ones(num_cameras, np.int64)

    def find(i):
        root = i
        while parent[root] != root:
            root = parent[root]
        while parent[i] != root:  # path compression
            parent[i], i = root, parent[i]
        return root

    if cam_idx.size and cap > 1:
        order = np.argsort(pt_idx, kind="stable")
        ps, cs = pt_idx[order], cam_idx[order]
        adj = ps[1:] == ps[:-1]
        a, b = cs[:-1][adj], cs[1:][adj]
        neq = a != b
        a, b = a[neq], b[neq]
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        pairs, counts = np.unique(lo * num_cameras + hi, return_counts=True)
        for key in pairs[np.argsort(-counts, kind="stable")]:
            ra, rb = find(key // num_cameras), find(key % num_cameras)
            if ra != rb and size[ra] + size[rb] <= cap:
                parent[rb] = ra
                size[ra] += size[rb]

    roots = np.asarray([find(i) for i in range(num_cameras)])
    _, cluster = np.unique(roots, return_inverse=True)
    return cluster.astype(np.int32)


def build_cluster_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    target: int = 0,
    mask: Optional[np.ndarray] = None,
    world_size: int = 1,
) -> ClusterPlan:
    """Plan the two-level coarse space over one (possibly padded) edge
    stream.  `cam_idx`/`pt_idx` are in the SOLVER's final edge order
    (post-sort/-plan, padding included, `world_size` equal contiguous
    shards when sharded); `mask` marks real edges — padding edges get
    the inert pc-slot n_pc, so the device scatter drops them (their
    data rows are zero anyway)."""
    cam_idx = np.asarray(cam_idx, np.int64)
    pt_idx = np.asarray(pt_idx, np.int64)
    n_edges = int(cam_idx.shape[0])
    cluster = build_camera_clusters(
        cam_idx, pt_idx, num_cameras, target, mask)
    C = int(cluster.max()) + 1 if num_cameras else 1

    real = (np.ones(n_edges, bool) if mask is None
            else np.asarray(mask) > 0)
    key = pt_idx * C + cluster[cam_idx]  # (point, cluster) incidence id
    uniq, inv = np.unique(key[real], return_inverse=True)
    n_pc = int(uniq.shape[0])
    pc_slot = np.full(n_edges, n_pc, np.int32)
    pc_slot[real] = inv.astype(np.int32)
    pc_pt = (uniq // C).astype(np.int32)
    pc_cluster = (uniq % C).astype(np.int32)

    # ec-pairs: for every real edge e, one entry per pc-slot of pt(e)
    # (the incidences of one point are contiguous in the sorted uniq
    # keys).  Σ_e k_{pt(e)} entries, k_p = number of distinct clusters
    # seeing point p — a small multiple of nE under co-observation
    # clustering.
    pts, pstarts, pcounts = np.unique(pc_pt, return_index=True,
                                      return_counts=True)
    start_of_pt = np.zeros(max(num_points, 1), np.int64)
    count_of_pt = np.zeros(max(num_points, 1), np.int64)
    start_of_pt[pts] = pstarts
    count_of_pt[pts] = pcounts
    edge_ids = np.nonzero(real)[0]
    k_of_edge = count_of_pt[pt_idx[edge_ids]]
    n_ec = int(k_of_edge.sum())
    ec_edge_g = np.repeat(edge_ids, k_of_edge)
    off = np.arange(n_ec, dtype=np.int64) - np.repeat(
        np.cumsum(k_of_edge) - k_of_edge, k_of_edge)
    ec_slot = (start_of_pt[pt_idx[ec_edge_g]] + off).astype(np.int32)
    ec_seg = (cam_idx[ec_edge_g] * C
              + pc_cluster[ec_slot]).astype(np.int32)

    # Shard-group the pairs: each pair belongs to its edge's shard
    # (equal contiguous edge shards), shard groups are padded to the
    # common max with inert entries and edge ids are made SHARD-LOCAL,
    # so a P(EDGE_AXIS) split of the ec arrays is self-consistent.
    ws = max(1, int(world_size))
    if n_edges % ws:
        # The documented precondition, made LOUD: a ragged edge stream
        # would silently assign the tail edges to a shard the grouping
        # loop never collects, dropping their coupling terms from G.
        # flat_solve always pads to ws*EDGE_QUANTUM before planning;
        # direct callers must do the same.
        raise ValueError(
            f"cluster plan needs world_size ({ws}) equal contiguous "
            f"edge shards, got {n_edges} edges (not divisible); pad "
            "the edge stream first (core.types.pad_edges)")
    shard_edges = n_edges // ws
    shard_of = ec_edge_g // max(shard_edges, 1)
    groups = []
    for k in range(ws):
        sel = shard_of == k
        groups.append((ec_edge_g[sel] - k * shard_edges,
                       ec_slot[sel], ec_seg[sel]))
    L = max(1, max(g[0].shape[0] for g in groups))
    ee, es, eg = [], [], []
    for local_e, slot, seg in groups:
        pad = L - local_e.shape[0]
        ee.append(np.concatenate(
            [local_e, np.zeros(pad, np.int64)]).astype(np.int32))
        es.append(np.concatenate([slot, np.zeros(pad, np.int32)]))
        # Out-of-range segment: the scatter (mode="drop") ignores it.
        eg.append(np.concatenate(
            [seg, np.full(pad, num_cameras * C, np.int32)]))
    return ClusterPlan(
        num_cameras=num_cameras, num_clusters=C, n_pc=max(n_pc, 1),
        n_ec=n_ec, world_size=ws, cluster=cluster, pc_slot=pc_slot,
        pc_pt=(pc_pt if n_pc else np.zeros(1, np.int32)),
        ec_edge=np.concatenate(ee), ec_slot=np.concatenate(es),
        ec_seg=np.concatenate(eg))


def cached_cluster_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    target: int = 0,
    mask: Optional[np.ndarray] = None,
    world_size: int = 1,
    smooth_omega: float = 0.0,
):
    """`build_cluster_plan` behind the host plan cache.

    Returns ((ClusterPlan, DeviceClusterPlan), cache_hit) — keyed by a
    blake2b content fingerprint of the index arrays + mask + EVERY
    aggregation parameter (target, world_size, smoothing omega),
    exactly like the tile plans, so repeated solves of one problem
    (benchmark windows, chunked drivers, the auditor's canonical lowerings)
    build the cluster graph once.  `smooth_omega` does not change the
    plan CONTENT today (smoothing is a device-side build step over the
    planned indices), but it is part of the key by contract: a
    SolverOption knob flip must never be able to serve a stale plan
    from the LRU, including under future plans that do consume it."""
    def build():
        plan = build_cluster_plan(cam_idx, pt_idx, num_cameras, num_points,
                                  target, mask, world_size=world_size)
        return plan, device_cluster_plan(plan)

    return _cached_plan(
        "cluster",
        (cam_idx, pt_idx, None if mask is None else np.asarray(mask) > 0),
        (int(num_cameras), int(num_points), int(target), int(world_size),
         float(smooth_omega)), build)


# ---------------------------------------------------------------------------
# Recursive camera-graph hierarchy (MULTILEVEL Schur preconditioner)
# ---------------------------------------------------------------------------
#
# The L-level preconditioner (solver/precond.py) re-aggregates the
# level-1 cluster graph recursively: level l+1's "cameras" are level
# l's clusters, and the co-observation weights between them are exactly
# the camera co-observation weights with cameras relabelled by their
# cluster — so every level reuses build_camera_clusters over the SAME
# edge stream with relabelled camera ids.  All of it is host graph
# work, planned once and cached; on device the extra levels are just
# tiny replicated [C_l] assignment gathers (dense Galerkin contractions
# in solver/precond.py), so the hierarchy adds no per-edge state and no
# collectives anywhere.


@dataclasses.dataclass(frozen=True)
class MultiLevelPlan:
    """Host half of the recursive camera-cluster hierarchy.

    `base` is the level-1 plan (cameras -> C_1 clusters, with the
    pc/ec streams the device Galerkin build consumes);
    `level_sizes[i]` is the cluster count of coarse level i+1
    (level_sizes[0] == base.num_clusters), and `assign[i]` maps level
    i+1's blocks onto level i+2's clusters ([level_sizes[i]] int32).
    Total hierarchy depth = 1 (fine) + len(level_sizes)."""

    base: ClusterPlan
    level_sizes: Tuple[int, ...]
    assign: Tuple[np.ndarray, ...]


@dataclasses.dataclass(frozen=True)
class DeviceMultiLevelPlan:
    """Device half: the level-1 DeviceClusterPlan + per-level
    assignment arrays, registered as a pytree so the whole hierarchy
    rides jit/shard_map as ONE operand (like DualPlans)."""

    base: DeviceClusterPlan
    level_sizes: Tuple[int, ...]
    assign: Tuple[jax.Array, ...]


jax.tree_util.register_dataclass(
    DeviceMultiLevelPlan,
    data_fields=["base", "assign"],
    meta_fields=["level_sizes"],
)


def device_multilevel_plan(plan: MultiLevelPlan) -> DeviceMultiLevelPlan:
    return DeviceMultiLevelPlan(
        base=device_cluster_plan(plan.base),
        level_sizes=plan.level_sizes,
        assign=tuple(jnp.asarray(a) for a in plan.assign),
    )


def multilevel_partition_specs(mplan: DeviceMultiLevelPlan, edge_spec=None):
    """shard_map in_specs tree for a DeviceMultiLevelPlan operand: the
    level-1 plan follows `cluster_partition_specs`; the coarse
    assignment tables ride replicated (every level >= 2 is identical
    tiny dense work per shard)."""
    from jax.sharding import PartitionSpec as P

    return DeviceMultiLevelPlan(
        base=cluster_partition_specs(mplan.base, edge_spec=edge_spec),
        level_sizes=mplan.level_sizes,
        assign=tuple(P() for _ in mplan.assign),
    )


def coarse_plan_partition_specs(plan, edge_spec=None):
    """Partition specs for either coarse-space plan operand kind."""
    if isinstance(plan, DeviceMultiLevelPlan):
        return multilevel_partition_specs(plan, edge_spec=edge_spec)
    return cluster_partition_specs(plan, edge_spec=edge_spec)


def build_multilevel_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    target: int = 0,
    mask: Optional[np.ndarray] = None,
    world_size: int = 1,
    coarsen_factor: float = 4.0,
    max_levels: int = 3,
) -> MultiLevelPlan:
    """Plan the recursive hierarchy over one (padded) edge stream.

    Level 1 is `build_cluster_plan` (same contract); each further level
    aggregates the previous level's cluster graph toward
    `ceil(C / coarsen_factor)` clusters, stopping at `max_levels` total
    levels (fine included), when the graph stops shrinking, or when the
    coarsest space is already trivial (<= 2 blocks — a dense solve of 2
    blocks is cheaper than another level's bookkeeping)."""
    if not coarsen_factor > 1.0:
        raise ValueError(
            f"coarsen_factor must be > 1, got {coarsen_factor}")
    if max_levels < 2:
        raise ValueError(f"max_levels must be >= 2, got {max_levels}")
    base = build_cluster_plan(cam_idx, pt_idx, num_cameras, num_points,
                              target, mask, world_size=world_size)
    sizes = [base.num_clusters]
    assign: list = []
    edge_cl = base.cluster[np.asarray(cam_idx, np.int64)]
    while len(sizes) + 1 < max_levels and sizes[-1] > 2:
        cur = sizes[-1]
        tgt = max(1, int(np.ceil(cur / coarsen_factor)))
        if tgt >= cur:
            break
        nxt = build_camera_clusters(edge_cl, pt_idx, cur, tgt, mask)
        C = int(nxt.max()) + 1
        if C >= cur:
            break  # aggregation found nothing to merge
        assign.append(nxt.astype(np.int32))
        sizes.append(C)
        edge_cl = nxt[edge_cl]
    return MultiLevelPlan(base=base, level_sizes=tuple(sizes),
                          assign=tuple(assign))


def cached_multilevel_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    target: int = 0,
    mask: Optional[np.ndarray] = None,
    world_size: int = 1,
    coarsen_factor: float = 4.0,
    max_levels: int = 3,
    smooth_omega: float = 0.0,
):
    """`build_multilevel_plan` behind the host plan cache.

    Returns ((MultiLevelPlan, DeviceMultiLevelPlan), cache_hit).  The
    fingerprint includes EVERY aggregation parameter — target,
    world_size, coarsen_factor, max_levels AND the smoothing omega —
    so flipping any SolverOption preconditioner knob can never serve a
    stale hierarchy from the LRU (the coarse level count and cluster
    shapes are baked into the compiled program's operand shapes)."""
    def build():
        plan = build_multilevel_plan(
            cam_idx, pt_idx, num_cameras, num_points, target, mask,
            world_size=world_size, coarsen_factor=coarsen_factor,
            max_levels=max_levels)
        return plan, device_multilevel_plan(plan)

    return _cached_plan(
        "multilevel",
        (cam_idx, pt_idx, None if mask is None else np.asarray(mask) > 0),
        (int(num_cameras), int(num_points), int(target), int(world_size),
         float(coarsen_factor), int(max_levels), float(smooth_omega)),
        build)


# ---------------------------------------------------------------------------
# 2-D camera-tile plan (camera x edge mesh distribution)
# ---------------------------------------------------------------------------
#
# The 2-D mesh lowering (parallel/mesh.make_mesh_2d + SolverOption.
# mesh_2d) factors the world into edge_shards x cam_blocks and tiles the
# camera range into cam_blocks contiguous blocks.  This plan is the host
# half: it assigns every edge to the camera COLUMN owning its camera's
# tile, orders each column's edges co-observation-first (PI-BA, arXiv
# 1905.02373: camera-major, point-minor — each fetched point shard is
# fully consumed before the stream moves to the next), pads columns to a
# common quantum-aligned length, and lays the device blocks out
# edge-major/camera-minor — exactly the block order a
# P(None, (EDGE_AXIS, CAM_AXIS)) shard_map split produces.
#
# The device half additionally carries, per device, the point-SHARD
# buckets of its local edges (slot/point-local/mask triples padded to a
# common width): the double-buffered matvec tile loop
# (solver/pcg.make_matvec_2d) contracts bucket s while the collective
# fetching shard s+1 is already in flight, so the ICI transfer of the
# next tile overlaps the MXU contraction of the current one.


def coobservation_edge_order(cam_idx: np.ndarray,
                             pt_idx: np.ndarray) -> np.ndarray:
    """PI-BA co-observation-first edge permutation (camera-major,
    point-minor, stable).

    Edges sharing a camera become contiguous and, within one camera,
    edges touching nearby points cluster — the ordering that maximises
    tile reuse before any transfer (arXiv 1905.02373).  Pure host
    argsort; applying it only reorders summation (results agree at
    solver tolerance, never bitwise).
    """
    return np.lexsort((np.asarray(pt_idx), np.asarray(cam_idx)))


def edge_stream_reuse(cam_idx: np.ndarray,
                      pt_idx: np.ndarray,
                      cam_tile: int,
                      pt_tile: int,
                      mask: Optional[np.ndarray] = None) -> dict:
    """Streaming tile-reuse statistics of one edge order.

    Model: a consumer walks the edge stream holding ONE (camera-tile,
    point-tile) pair resident; every time a consecutive edge needs a
    different pair it pays a tile transfer.  `switches` counts those
    transitions (the first edge's fetch included), `reuse_factor` is
    edges consumed per fetched pair — the quantity the co-observation
    ordering (EdgeOrder.COOBS) strictly improves on locality-structured
    scenes, and the honest denominator of the 2-D plan's "each gathered
    tile fully consumed" claim.
    """
    cam_idx = np.asarray(cam_idx, np.int64)
    pt_idx = np.asarray(pt_idx, np.int64)
    if mask is not None:
        keep = np.asarray(mask) > 0
        cam_idx, pt_idx = cam_idx[keep], pt_idx[keep]
    n = int(cam_idx.shape[0])
    if n == 0:
        return {"edges": 0, "switches": 0, "reuse_factor": 0.0}
    key = (cam_idx // max(1, int(cam_tile)),
           pt_idx // max(1, int(pt_tile)))
    changed = (key[0][1:] != key[0][:-1]) | (key[1][1:] != key[1][:-1])
    switches = int(changed.sum()) + 1  # first fetch counts
    return {"edges": n, "switches": switches,
            "reuse_factor": float(n) / float(switches)}


@dataclasses.dataclass(frozen=True)
class CameraTilePlan:
    """Host half of the 2-D camera x edge distribution plan.

    The padded edge stream (length `n_edges_padded` =
    cam_blocks * column_len) is addressed THROUGH `perm`/`mask`:
    position i of the stream carries caller edge `perm[i]` when
    `mask[i] > 0` and inert padding otherwise.  Device block b of a
    P((EDGE_AXIS, CAM_AXIS)) split (b = edge_shard * cam_blocks +
    cam_block) is the contiguous slice [b*chunk, (b+1)*chunk) — the
    plan lays columns out so that block b holds edge-shard
    b // cam_blocks of camera column b % cam_blocks.
    """

    num_cameras: int
    num_points: int
    edge_shards: int  # E
    cam_blocks: int  # C
    tile_cams: int  # Tc: cameras per tile (C * Tc >= Nc)
    shard_points: int  # Sp: points per shard (C * Sp >= Np)
    n_edges_real: int
    n_edges_padded: int
    bucket_width: int  # Lb
    perm: np.ndarray  # [nE_pad] int64 caller edge per stream slot
    mask: np.ndarray  # [nE_pad] float64 1=real 0=padding
    cam_idx: np.ndarray  # [nE_pad] int32 GLOBAL camera per slot
    pt_idx: np.ndarray  # [nE_pad] int32 GLOBAL point per slot
    cam_local: np.ndarray  # [nE_pad] int32 tile-LOCAL camera per slot
    bucket_slot: np.ndarray  # [E*C*C, Lb] int32 device-local edge slot
    bucket_ptl: np.ndarray  # [E*C*C, Lb] int32 shard-LOCAL point
    bucket_mask: np.ndarray  # [E*C*C, Lb] int32 1=real pair
    # Streaming-reuse statistics of the final stream.
    reuse: dict


@dataclasses.dataclass(frozen=True)
class DeviceCameraTilePlan:
    """Device half: static tile geometry + index streams, registered as
    a pytree so the whole plan rides jit/shard_map as ONE operand."""

    cam_blocks: int
    tile_cams: int
    shard_points: int
    cam_local: jax.Array  # [nE] int32 (edge axis; device-local slice)
    bucket_slot: jax.Array  # [C, Lb] int32 per device after the split
    bucket_ptl: jax.Array  # [C, Lb] int32
    bucket_mask: jax.Array  # [C, Lb] int32


jax.tree_util.register_dataclass(
    DeviceCameraTilePlan,
    data_fields=["cam_local", "bucket_slot", "bucket_ptl", "bucket_mask"],
    meta_fields=["cam_blocks", "tile_cams", "shard_points"],
)


def device_camera_tile_plan(plan: CameraTilePlan) -> DeviceCameraTilePlan:
    return DeviceCameraTilePlan(
        cam_blocks=plan.cam_blocks,
        tile_cams=plan.tile_cams,
        shard_points=plan.shard_points,
        cam_local=jnp.asarray(plan.cam_local),
        bucket_slot=jnp.asarray(plan.bucket_slot),
        bucket_ptl=jnp.asarray(plan.bucket_ptl),
        bucket_mask=jnp.asarray(plan.bucket_mask),
    )


def tile_plan_partition_specs(tplan: DeviceCameraTilePlan, edge_spec):
    """shard_map in_specs tree for a DeviceCameraTilePlan operand: the
    per-edge cam_local stream follows the 2-D edge split, and the
    per-device bucket tables split the same way on their leading axis
    (the builder stacked them in device-block order, cam_blocks rows
    per device)."""
    return DeviceCameraTilePlan(
        cam_blocks=tplan.cam_blocks, tile_cams=tplan.tile_cams,
        shard_points=tplan.shard_points, cam_local=edge_spec,
        bucket_slot=edge_spec, bucket_ptl=edge_spec,
        bucket_mask=edge_spec)


def build_camera_tile_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    edge_shards: int,
    cam_blocks: int,
    quantum: int = 0,
) -> CameraTilePlan:
    """Plan the 2-D camera x edge distribution over a caller edge set.

    Edges land in the camera column owning their camera's tile
    (contiguous tiles of `tile_cams = ceil(Nc / cam_blocks)` cameras),
    ordered co-observation-first within each column, and every column
    is padded to one common length — a multiple of
    `edge_shards * quantum` (quantum defaults to core.fm.EDGE_QUANTUM,
    matching the 1-D shard padding) — so each of the E*C device chunks
    is equal-size and the chunked Schur build's slices stay
    static-shape.  Padding slots repeat the column's LAST real camera
    (keeping every per-device stream camera-sorted for the
    indices_are_sorted scatter promise) and point 0, under mask 0.
    """
    from megba_tpu.core.fm import EDGE_QUANTUM

    if quantum <= 0:
        quantum = EDGE_QUANTUM
    E = int(edge_shards)
    C = int(cam_blocks)
    if E < 1 or C < 1:
        raise ValueError(
            f"edge_shards and cam_blocks must be >= 1, got {E} x {C}")
    cam_idx = np.asarray(cam_idx, np.int64)
    pt_idx = np.asarray(pt_idx, np.int64)
    n_real = int(cam_idx.shape[0])
    Tc = max(1, -(-int(num_cameras) // C))
    Sp = max(1, -(-int(num_points) // C))
    col = np.minimum(cam_idx // Tc, C - 1)

    # Column streams: co-observation order (camera-major, point-minor)
    # inside each column, padded to the common quantum-aligned length.
    col_ids = []
    for c in range(C):
        ids = np.nonzero(col == c)[0]
        ids = ids[coobservation_edge_order(cam_idx[ids], pt_idx[ids])]
        col_ids.append(ids)
    Lc = max(1, max(ids.shape[0] for ids in col_ids))
    Lc = -(-Lc // (E * quantum)) * (E * quantum)
    chunk = Lc // E

    perm = np.zeros(C * Lc, np.int64)
    mask = np.zeros(C * Lc, np.float64)
    cam_s = np.zeros(C * Lc, np.int32)
    pt_s = np.zeros(C * Lc, np.int32)
    cam_l = np.zeros(C * Lc, np.int32)
    pos = 0
    # Device-block order: edge-shard-major, camera-minor (the order a
    # P((EDGE_AXIS, CAM_AXIS)) split hands to device (e, c) = block
    # e*C + c).
    for e in range(E):
        for c in range(C):
            ids = col_ids[c]
            seg = ids[e * chunk:(e + 1) * chunk]
            n = seg.shape[0]
            sl = slice(pos, pos + chunk)
            perm[sl][:n] = seg
            mask[pos:pos + n] = 1.0
            # Padding cameras: the column's last REAL camera (stream
            # stays sorted, index stays inside the tile); a column with
            # no real edges anchors to its tile's first in-range camera.
            if ids.shape[0]:
                pad_cam = int(cam_idx[ids[-1]])
            else:
                pad_cam = min(c * Tc, max(0, int(num_cameras) - 1))
            cams = np.full(chunk, pad_cam, np.int32)
            cams[:n] = cam_idx[seg]
            pts = np.zeros(chunk, np.int32)
            pts[:n] = pt_idx[seg]
            cam_s[sl] = cams
            pt_s[sl] = pts
            cam_l[sl] = np.clip(cams - c * Tc, 0, Tc - 1)
            pos += chunk

    # Per-device point-shard buckets over the REAL local edges.
    n_dev = E * C
    rows = []
    for d in range(n_dev):
        sl = slice(d * chunk, (d + 1) * chunk)
        ptd, md = pt_s[sl], mask[sl]
        rows.append([
            np.nonzero((ptd // Sp == s) & (md > 0))[0] for s in range(C)
        ])
    Lb = max(1, max(max((r.shape[0] for r in dev), default=0)
                    for dev in rows))
    b_slot = np.zeros((n_dev * C, Lb), np.int32)
    b_ptl = np.zeros((n_dev * C, Lb), np.int32)
    b_mask = np.zeros((n_dev * C, Lb), np.int32)
    for d, dev in enumerate(rows):
        for s, sel in enumerate(dev):
            n = sel.shape[0]
            b_slot[d * C + s, :n] = sel
            b_ptl[d * C + s, :n] = pt_s[d * chunk + sel] - s * Sp
            b_mask[d * C + s, :n] = 1

    # Per-DEVICE streaming stats: every device walks its own chunk and
    # pays its own first fetch, so the metric is aggregated over the
    # E*C independent block walks — one concatenated walk would charge
    # a phantom switch at every device-block boundary.
    edges_t = switches_t = 0
    for d in range(n_dev):
        sl = slice(d * chunk, (d + 1) * chunk)
        r = edge_stream_reuse(cam_s[sl], pt_s[sl], Tc, Sp, mask=mask[sl])
        edges_t += r["edges"]
        switches_t += r["switches"]
    reuse = {"edges": edges_t, "switches": switches_t,
             "reuse_factor": float(edges_t) / float(max(switches_t, 1))}
    return CameraTilePlan(
        num_cameras=int(num_cameras), num_points=int(num_points),
        edge_shards=E, cam_blocks=C, tile_cams=Tc, shard_points=Sp,
        n_edges_real=n_real, n_edges_padded=C * Lc, bucket_width=Lb,
        perm=perm, mask=mask, cam_idx=cam_s, pt_idx=pt_s,
        cam_local=cam_l, bucket_slot=b_slot, bucket_ptl=b_ptl,
        bucket_mask=b_mask, reuse=reuse)


def cached_camera_tile_plan(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    num_cameras: int,
    num_points: int,
    edge_shards: int,
    cam_blocks: int,
    quantum: int = 0,
    timer=None,
):
    """`build_camera_tile_plan` behind the host plan cache.

    Returns ((CameraTilePlan, DeviceCameraTilePlan), cache_hit), keyed
    by a blake2b content fingerprint of the index arrays plus EVERY
    geometry knob, exactly like the tile/cluster plans."""
    def build():
        plan = build_camera_tile_plan(
            cam_idx, pt_idx, num_cameras, num_points, edge_shards,
            cam_blocks, quantum=quantum)
        return plan, device_camera_tile_plan(plan)

    return _cached_plan(
        "mesh2d", (cam_idx, pt_idx),
        (int(num_cameras), int(num_points), int(edge_shards),
         int(cam_blocks), int(quantum)), build, timer)


# Kernel-vs-fallback agreement bound, relative to the fallback's
# magnitude: max|kernel - fallback| / (1 + max|fallback|).  Set between
# the two readings of the v5e (chip runs, PR 21): correct kernels read at
# most 2.369e-7; kernels whose one-hot dots ran at default precision
# (operands rounded to bf16) read 6.269e-4 (jtj_grad_reduce, cam) to
# 2.136e-3 (coupling_reduce, cam).  A lost Precision.HIGHEST fails it.
KERNEL_CHECK_TOL = 1e-5


class KernelMismatch(RuntimeError):
    """A Pallas kernel compiled but disagreed with its XLA fallback."""


def rel_err(got: jax.Array, want: jax.Array) -> float:
    """max|got - want| / (1 + max|want|), the kernel-check measure."""
    return float(jnp.max(jnp.abs(got - want))
                 / (1.0 + jnp.max(jnp.abs(want))))


def require_all_close(results, tol: float = KERNEL_CHECK_TOL) -> None:
    """Raise KernelMismatch naming every (kernel, geometry, rel_err)
    result over `tol` (NaN included); the message lists all results."""
    bad = [r for r in results if not r[2] <= tol]
    if bad:
        lines = "\n".join(f"  {n} [{g}]: {e:.3e}" for n, g, e in results)
        raise KernelMismatch(
            f"{len(bad)} kernel(s) differ from their XLA fallback by more "
            f"than {tol:.0e} (relative):\n{lines}")


def check_kernels(interpret: bool = False):
    """Run all five kernels against their XLA fallbacks; returns
    [(kernel, geometry, rel_err)] and raises KernelMismatch listing them
    all when any disagrees.  A kernel that fails to compile raises its
    own error.

    Covers tile_reduce, tile_expand, jtj_grad_reduce, coupling_expand
    and coupling_reduce at BOTH production plan geometries: the camera
    side (DEFAULT_TILE_CAM / DEFAULT_BLOCK_CAM, d=9, od=2 — 18- and
    90-row blocks) and the point side (DEFAULT_TILE_PT /
    DEFAULT_BLOCK_PT, d=3 — 6- and 12-row blocks).  None of these row
    counts are sublane multiples, and Mosaic rejections are
    shape-dependent, so toy shapes would not certify the shapes the
    solve compiles.  The point side runs twice: on a stream that
    observes every point 5 times, as venice's, whose tiles span about
    205 ids and so take the solve's 384-wide one-hot window (the last
    tile of a block clamped at its end), and on a sparse random stream
    whose tiles span their whole block.  The camera blocks take the
    whole block.  `interpret=True` runs the same kernel bodies under the
    Pallas interpreter (the CPU test of this check).
    """
    rng = np.random.default_rng(0)
    out = []
    for side, tile, block, ns, d, od, degree in (
        ("cam", DEFAULT_TILE_CAM, DEFAULT_BLOCK_CAM, 200, 9, 2, 0),
        ("pt", DEFAULT_TILE_PT, DEFAULT_BLOCK_PT, 8000, 3, 2, 0),
        ("pt", DEFAULT_TILE_PT, DEFAULT_BLOCK_PT, 2400, 3, 2, 5),
    ):
        if degree:
            idx = rng.permutation(np.repeat(np.arange(ns), degree))
        else:
            # Several tiles; some blocks get >1 (accumulate).
            idx = rng.integers(0, ns, 4 * tile)
        plan = build_tile_plan(idx, ns, tile=tile, block=block)
        geometry = (f"{side} tile={tile} block={block} "
                    f"window={plan.window} d={d}")
        dp = device_plan(plan)
        m = jnp.asarray(plan.mask)

        def normal(*shape):
            return jnp.asarray(
                rng.standard_normal(shape).astype(np.float32))

        data = normal(3, plan.n_slots) * m
        table = normal(3, ns)
        J = normal(od * d, plan.n_slots) * m
        r = normal(od, plan.n_slots) * m
        vt = normal(d, ns)
        u = normal(od, plan.n_slots) * m
        h_k, g_k = jtj_grad_reduce(J, r, dp, use_kernels=True,
                                   interpret=interpret)
        h_f, g_f = jtj_grad_reduce(J, r, dp, use_kernels=False)
        pairs = (
            ("tile_reduce", tile_reduce(data, dp, interpret=interpret),
             reduce_fallback(data, dp)),
            ("tile_expand", tile_expand(table, dp, interpret=interpret) * m,
             expand_fallback(table, dp) * m),
            ("jtj_grad_reduce", jnp.concatenate([h_k, g_k]),
             jnp.concatenate([h_f, g_f])),
            ("coupling_expand",
             coupling_expand(vt, J, dp, d, use_kernels=True,
                             interpret=interpret) * m,
             coupling_expand(vt, J, dp, d, use_kernels=False) * m),
            ("coupling_reduce",
             coupling_reduce(J, u, dp, d, use_kernels=True,
                             interpret=interpret),
             coupling_reduce(J, u, dp, d, use_kernels=False)),
        )
        out += [(name, geometry, rel_err(got, want))
                for name, got, want in pairs]
    require_all_close(out)
    return out


@functools.lru_cache(maxsize=1)
def probe_kernels() -> bool:
    """The `use_kernels` default: True on a TPU, False elsewhere.

    On a TPU every kernel is first compiled and checked against its XLA
    fallback (`check_kernels`); a compile error or a mismatch raises —
    the solve never drops to the fallback behind the caller's back.
    Off the TPU it returns False without compiling anything: the CPU
    lane runs the XLA fallback (interpret mode is correct but far
    slower).
    """
    if jax.default_backend() != "tpu":
        return False
    check_kernels()
    return True
