"""Fused edge-pipeline mega-kernels: gather -> contract -> scatter in ONE
Pallas kernel per direction (ISSUE 19 / ROADMAP raw-speed item).

The tiled Schur matvec of ops/segtiles runs the coupling product as
separate passes — expand the Krylov vector to edges, contract the W /
Jc·Jp rows, reduce onto the output segments — so every PCG iteration
streams the co-observation-ordered edge tiles through HBM up to three
times.  This module fuses all three stages: each grid step holds one
edge tile resident in VMEM, one-hot-gathers its input-block rows,
contracts the coupling rows against them, and one-hot-scatters the
result onto its output block, all before the tile leaves VMEM.  The
per-edge expanded rows NEVER touch HBM.

The price of full fusion is plan structure.  A single kernel needs BOTH
one-hots block-sized, so every edge tile must live inside one
(input_block, output_block) BUCKET — the `build_camera_tile_plan` idea
applied to the 1-D edge stream.  `build_fused_plan` sorts edges
output-block-major (Pallas accumulates into an output block only across
CONSECUTIVE grid steps), input-block-minor inside it (co-observation
locality survives the stable sort), and pads each bucket to whole
tiles.  Each matvec direction (cam->pt and pt->cam) needs its own
bucket order, so each direction carries its own edge permutation: the
coupling rows are re-permuted ONCE per PCG solve (`permute_rows`) and
then reused across every CG iteration.

Precision contract (the PR 15 Bf16Surface discipline, extended into the
kernel bodies): operand tiles may be bf16 — the one-hot gather runs as
a bf16 x bf16 MXU dot_general with `preferred_element_type=f32` (the
one-hot is exact in any dtype), per-edge products multiply in bf16 and
upcast — while EVERY accumulator is f32 (asserted at trace time inside
the kernels).  f64 operands accumulate in f64 (the CPU verification
lane).

Backend policy mirrors ops/segtiles: on TPU the kernels compile through
Mosaic (`tpu_custom_call`); on every other backend the SAME kernel
bodies run under Pallas interpret mode — that interpret-mode parity is
the CPU-lane certificate that the fused program computes the XLA
lowering's product (tests/test_fused.py pins the tolerances).
Wall-clock evidence is deferred to a TPU window; the transferable
evidence on the CPU lane is structural (the `flops_per_sp` /
`bytes_touched_per_sp` budget axes and the custom-call census in
analysis/).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default fused-plan geometry.  Smaller blocks than the segtiles
# defaults: the kernel keeps BOTH one-hots resident ([in_block, tile] +
# [out_block, tile] f32 ≈ 1.5 MB at these sizes), alongside the coupling
# rows ([cd*pd, tile]) and the vertex table block.  Smaller blocks cost
# more buckets (more tile padding) but bound VMEM; `_fit_tile` shrinks
# the tile for toy problems exactly like the segtiles planner.
FUSED_TILE = 512
FUSED_BLOCK_CAM = 256
FUSED_BLOCK_PT = 512


def kernels_supported() -> bool:
    """True iff the fused kernels COMPILE (Mosaic): on a TPU, always.
    Off-TPU the same kernels run interpreted — numerically the
    certificate lane, never the fast path."""
    return jax.default_backend() == "tpu"


def _fit_tile(t: int, n: int) -> int:
    """Shrink tile size t so it does not dwarf an n-edge problem
    (same policy as ops/segtiles._fit_tile)."""
    while t > 128 and t >= 4 * n:
        t //= 2
    return t


# ---------------------------------------------------------------------------
# Host plan: bucket-structured edge order for one matvec direction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """Static edge reordering for one fused gather->contract->scatter
    direction.

    `perm[s]` is the SOURCE edge-stream slot feeding fused slot s
    (padding slots point at 0 under mask 0); `in_local`/`out_local` are
    the block-local input/output segment of each slot; tile t reads
    input block `tile_in[t]`, accumulates into output block
    `tile_out[t]`, and initialises it when `tile_first[t]` — output
    blocks are visited in contiguous runs (the Pallas sequential-
    accumulation contract).
    """

    tile: int
    in_block: int
    out_block: int
    num_in_segments: int
    num_out_segments: int
    num_in_blocks: int
    num_out_blocks: int
    n_edges: int  # real (unmasked) edges routed through the plan
    perm: np.ndarray  # [n_slots] int32 source slot per fused slot
    mask: np.ndarray  # [n_slots] float32 1=real 0=padding
    in_local: np.ndarray  # [n_slots] int32
    out_local: np.ndarray  # [n_slots] int32
    tile_in: np.ndarray  # [n_tiles] int32
    tile_out: np.ndarray  # [n_tiles] int32
    tile_first: np.ndarray  # [n_tiles] int32

    @property
    def n_slots(self) -> int:
        return int(self.perm.shape[0])

    @property
    def n_tiles(self) -> int:
        return int(self.tile_in.shape[0])

    @property
    def occupancy(self) -> float:
        """Real edges per slot — the bucket-padding overhead metric."""
        return float(self.n_edges) / float(max(1, self.n_slots))


def build_fused_plan(
    in_idx: np.ndarray,
    out_idx: np.ndarray,
    mask: Optional[np.ndarray],
    num_in: int,
    num_out: int,
    tile: int = FUSED_TILE,
    in_block: int = FUSED_BLOCK_CAM,
    out_block: int = FUSED_BLOCK_PT,
    fit: bool = True,
) -> FusedPlan:
    """Plan one direction over a (possibly already padded) edge stream.

    `in_idx`/`out_idx` are per-slot segment ids of the SOURCE stream;
    slots with `mask <= 0` (the source plan's padding) are dropped.
    Every output block gets at least one tile (all-padding tail tiles
    for edgeless blocks) so the kernel initialises the whole output.
    """
    in_idx = np.asarray(in_idx, np.int64)
    out_idx = np.asarray(out_idx, np.int64)
    real = (np.asarray(mask) > 0 if mask is not None
            else np.ones(in_idx.shape[0], bool))
    edges = np.nonzero(real)[0]
    iin, iout = in_idx[edges], out_idx[edges]
    if fit:
        tile = _fit_tile(tile, max(1, edges.shape[0]))
    num_in_blocks = max(1, -(-num_in // in_block))
    num_out_blocks = max(1, -(-num_out // out_block))

    key = (iout // out_block) * num_in_blocks + (iin // in_block)
    order = np.argsort(key, kind="stable")
    uk, counts = np.unique(key[order], return_counts=True)
    padded = ((counts + tile - 1) // tile) * tile
    offsets = np.concatenate([[0], np.cumsum(padded[:-1])]).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts[:-1])]).astype(np.int64)
    within = np.arange(edges.shape[0]) - np.repeat(starts, counts)
    dest = np.repeat(offsets, counts) + within

    n_slots = int(padded.sum())
    perm = np.zeros(n_slots, np.int32)
    slot_mask = np.zeros(n_slots, np.float32)
    in_local = np.zeros(n_slots, np.int32)
    out_local = np.zeros(n_slots, np.int32)
    perm[dest] = edges[order].astype(np.int32)
    slot_mask[dest] = 1.0
    in_local[dest] = (iin[order] % in_block).astype(np.int32)
    out_local[dest] = (iout[order] % out_block).astype(np.int32)

    tiles_per_bucket = (padded // tile).astype(np.int64)
    tile_in = np.repeat(uk % num_in_blocks, tiles_per_bucket).astype(np.int32)
    tile_out = np.repeat(uk // num_in_blocks,
                         tiles_per_bucket).astype(np.int32)
    # Edgeless output blocks still need ONE initialising tile (the
    # kernel writes zeros: all-padding tile => zeroed coupling columns).
    # Appended at the END: revisit-consecutiveness only needs each
    # output block's tiles contiguous, not globally sorted.
    missing = np.setdiff1d(np.arange(num_out_blocks), np.unique(tile_out))
    if missing.size:
        perm = np.concatenate([perm, np.zeros(missing.size * tile, np.int32)])
        slot_mask = np.concatenate(
            [slot_mask, np.zeros(missing.size * tile, np.float32)])
        in_local = np.concatenate(
            [in_local, np.zeros(missing.size * tile, np.int32)])
        out_local = np.concatenate(
            [out_local, np.zeros(missing.size * tile, np.int32)])
        tile_in = np.concatenate(
            [tile_in, np.zeros(missing.size, np.int32)])
        tile_out = np.concatenate([tile_out, missing.astype(np.int32)])
        n_slots = int(perm.shape[0])

    n_tiles = int(tile_in.shape[0])
    tile_first = np.zeros(n_tiles, np.int32)
    if n_tiles:
        tile_first[0] = 1
        tile_first[1:] = (tile_out[1:] != tile_out[:-1]).astype(np.int32)

    return FusedPlan(
        tile=tile, in_block=in_block, out_block=out_block,
        num_in_segments=num_in, num_out_segments=num_out,
        num_in_blocks=num_in_blocks, num_out_blocks=num_out_blocks,
        n_edges=int(edges.shape[0]), perm=perm, mask=slot_mask,
        in_local=in_local, out_local=out_local, tile_in=tile_in,
        tile_out=tile_out, tile_first=tile_first)


@dataclasses.dataclass(frozen=True)
class DeviceFusedPlan:
    """Device half of a FusedPlan, registered as a pytree so both
    directions ride the solve program as ordinary operands (toggling
    `fused_kernels` never bakes indices into a compiled program)."""

    tile: int
    in_block: int
    out_block: int
    num_in_blocks: int
    num_out_blocks: int
    num_in_segments: int
    num_out_segments: int
    n_edges: int
    perm: jax.Array  # [n_slots] int32
    mask: jax.Array  # [n_slots] float32
    in_local: jax.Array  # [1, n_slots] int32 (kernel row-block layout)
    out_local: jax.Array  # [1, n_slots] int32
    tile_in: jax.Array  # [n_tiles] int32
    tile_out: jax.Array  # [n_tiles] int32
    tile_first: jax.Array  # [n_tiles] int32


jax.tree_util.register_dataclass(
    DeviceFusedPlan,
    data_fields=["perm", "mask", "in_local", "out_local",
                 "tile_in", "tile_out", "tile_first"],
    meta_fields=["tile", "in_block", "out_block", "num_in_blocks",
                 "num_out_blocks", "num_in_segments", "num_out_segments",
                 "n_edges"],
)


def device_fused_plan(plan: FusedPlan) -> DeviceFusedPlan:
    return DeviceFusedPlan(
        tile=plan.tile, in_block=plan.in_block, out_block=plan.out_block,
        num_in_blocks=plan.num_in_blocks,
        num_out_blocks=plan.num_out_blocks,
        num_in_segments=plan.num_in_segments,
        num_out_segments=plan.num_out_segments,
        n_edges=plan.n_edges,
        perm=jnp.asarray(plan.perm), mask=jnp.asarray(plan.mask),
        in_local=jnp.asarray(plan.in_local)[None, :],
        out_local=jnp.asarray(plan.out_local)[None, :],
        tile_in=jnp.asarray(plan.tile_in),
        tile_out=jnp.asarray(plan.tile_out),
        tile_first=jnp.asarray(plan.tile_first))


def build_fused_dual_plans(
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    mask: Optional[np.ndarray],
    num_cameras: int,
    num_points: int,
    tile: int = FUSED_TILE,
    block_cam: int = FUSED_BLOCK_CAM,
    block_pt: int = FUSED_BLOCK_PT,
) -> Tuple[FusedPlan, FusedPlan, DeviceFusedPlan, DeviceFusedPlan]:
    """Both directions over the canonical (cam-slot) edge stream.

    Returns (host_to_pt, host_to_cam, device_to_pt, device_to_cam):
    cam->pt gathers camera blocks and scatters point blocks; pt->cam
    the reverse.  Padding slots of the source stream (mask 0) are
    dropped — their coupling columns are zero by construction.
    """
    to_pt = build_fused_plan(
        cam_idx, pt_idx, mask, num_cameras, num_points,
        tile=tile, in_block=block_cam, out_block=block_pt)
    to_cam = build_fused_plan(
        pt_idx, cam_idx, mask, num_points, num_cameras,
        tile=tile, in_block=block_pt, out_block=block_cam)
    return to_pt, to_cam, device_fused_plan(to_pt), device_fused_plan(to_cam)


def permute_rows(rows: jax.Array, fplan: DeviceFusedPlan) -> jax.Array:
    """Reorder per-edge rows into one direction's fused slot order,
    zeroing padding slots.  Runs ONCE per PCG solve (the coupling rows
    are fixed across CG iterations) — the documented cost of carrying
    one bucket order per direction."""
    return (jnp.take(rows, fplan.perm, axis=1, mode="clip")
            * fplan.mask.astype(rows.dtype))


def fused_plan_summary(plan: FusedPlan) -> Dict[str, Any]:
    """JSON-able structure metrics of one direction (SolveReport)."""
    return {
        "tiles": plan.n_tiles,
        "tile": plan.tile,
        "occupancy": round(plan.occupancy, 4),
        "edges": plan.n_edges,
        "slots": plan.n_slots,
    }


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


# Precision of every one-hot matmul in the kernels (here and in
# ops/segtiles).  At default precision Mosaic rounds f32 operands to
# bf16: on a v5e every segtiles kernel then missed its XLA fallback by
# 6.3e-4 to 2.1e-3 relative (chip run, PR 21).  The one-hot side is
# exact in any dtype, so HIGHEST keeps the data side at full f32.
ONEHOT_PRECISION = jax.lax.Precision.HIGHEST


def kernel_out_shape(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A pallas_call output type that varies over every mesh axis its
    operands vary over.  Inside shard_map (check_vma on) a kernel's
    output must declare its varying axes; outside it the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _acc_dtype(*dtypes) -> jnp.dtype:
    """f32 accumulation for f32/bf16 operands, f64 for f64 (CPU lane)."""
    out = jnp.float32
    for d in dtypes:
        out = jnp.promote_types(out, d)
    if out not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
        out = jnp.dtype(jnp.float32)
    return out


def _gather_block(in_l_ref, table_ref, in_block, acc_dt):
    """One-hot gather of an input block's rows to tile columns.

    The one-hot is exact in every dtype, so a bf16 table rides a
    bf16 x bf16 MXU dot — with the f32 (f64 on the CPU lane)
    accumulator the precision contract requires.
    """
    tile = in_l_ref.shape[1]
    onehot = (
        in_l_ref[:, :] == jax.lax.broadcasted_iota(
            jnp.int32, (in_block, tile), 0)
    ).astype(table_ref.dtype)  # [Bi, T]
    return jax.lax.dot_general(
        table_ref[:, :], onehot, (((1,), (0,)), ((), ())),
        precision=ONEHOT_PRECISION, preferred_element_type=acc_dt,
    )  # [d_in, T] accumulator dtype


def _scatter_block(te, out_l_ref, out_ref, tf_ref, i, out_block):
    """One-hot scatter-accumulate of per-edge rows onto the tile's
    output block; init-vs-accumulate is predicated on tile_first (the
    consecutive-revisit contract)."""
    tile = out_l_ref.shape[1]
    onehot = (
        out_l_ref[:, :] == jax.lax.broadcasted_iota(
            jnp.int32, (out_block, tile), 0)
    ).astype(te.dtype)  # [Bo, T]
    partial = jax.lax.dot_general(
        te, onehot, (((1,), (1,)), ((), ())),
        precision=ONEHOT_PRECISION, preferred_element_type=te.dtype,
    )  # [d_out, Bo]

    @pl.when(tf_ref[i] == 1)
    def _init():
        out_ref[:, :] = partial.astype(out_ref.dtype)

    @pl.when(tf_ref[i] == 0)
    def _acc():
        out_ref[:, :] = (out_ref[:, :] + partial).astype(out_ref.dtype)


def _contract_rows(row, vec_rows, a, acc, acc_dt, bf16_operands):
    """One product row into the f32/f64 accumulator.  The bf16 arm
    multiplies IN bf16 (the MXU operand format — same grouping as
    pcg._edge_precision's acc(up(w) * v)) and upcasts the product; the
    full-precision arm upcasts the stored row first."""
    if bf16_operands:
        t = (row * vec_rows[a, :]).astype(acc_dt)
    else:
        t = row.astype(acc_dt) * vec_rows[a, :]
    out = t if acc is None else acc + t
    # The fused-kernel precision contract: accumulators NEVER narrow.
    assert out.dtype == acc_dt, (out.dtype, acc_dt)
    return out


def _fused_w_kernel(ti_ref, to_ref, tf_ref, in_l_ref, out_l_ref, w_ref,
                    table_ref, out_ref, *, in_block, out_block, d_out,
                    w_in_major, bf16_operands, acc_dt):
    """EXPLICIT fused direction: te[b] = sum_a W[row(a,b)] * pe[a],
    gathered and scattered without leaving VMEM.

    W layout is [a*pd + b] (a = camera dim, b = point dim): cam->pt
    consumes it input-major (`w_in_major=True`), pt->cam output-major.
    """
    i = pl.program_id(0)
    d_in = table_ref.shape[0]
    pe = _gather_block(in_l_ref, table_ref, in_block, acc_dt)
    pe_op = pe.astype(jnp.bfloat16) if bf16_operands else pe
    rows = []
    for b in range(d_out):
        acc = None
        for a in range(d_in):
            r = (a * d_out + b) if w_in_major else (b * d_in + a)
            acc = _contract_rows(w_ref[r, :], pe_op, a, acc, acc_dt,
                                 bf16_operands)
        rows.append(acc[None, :])
    te = jnp.concatenate(rows, axis=0)  # [d_out, T] accumulator dtype
    _scatter_block(te, out_l_ref, out_ref, tf_ref, i, out_block)


def _fused_j_kernel(ti_ref, to_ref, tf_ref, in_l_ref, out_l_ref, jin_ref,
                    jout_ref, table_ref, out_ref, *, in_block, out_block,
                    d_out, bf16_operands, acc_dt):
    """IMPLICIT fused direction: u[o] = sum_a Jin[o*d_in+a] * pe[a],
    te[b] = sum_o Jout[o*d_out+b] * u[o] — the J_in product AND the
    J_out^T product happen on the same resident tile."""
    i = pl.program_id(0)
    d_in = table_ref.shape[0]
    od = jin_ref.shape[0] // d_in
    pe = _gather_block(in_l_ref, table_ref, in_block, acc_dt)
    pe_op = pe.astype(jnp.bfloat16) if bf16_operands else pe
    us = []
    for o in range(od):
        acc = None
        for a in range(d_in):
            acc = _contract_rows(jin_ref[o * d_in + a, :], pe_op, a, acc,
                                 acc_dt, bf16_operands)
        us.append(acc[None, :])
    u = jnp.concatenate(us, axis=0)  # [od, T] accumulator dtype
    u_op = u.astype(jnp.bfloat16) if bf16_operands else u
    rows = []
    for b in range(d_out):
        acc = None
        for o in range(od):
            acc = _contract_rows(jout_ref[o * d_out + b, :], u_op, o, acc,
                                 acc_dt, bf16_operands)
        rows.append(acc[None, :])
    te = jnp.concatenate(rows, axis=0)
    _scatter_block(te, out_l_ref, out_ref, tf_ref, i, out_block)


def _block_diag_kernel(h_ref, x_ref, out_ref, *, d, bf16_operands, acc_dt):
    """Fused block-diagonal apply: out[i] = sum_j H[i,j] * x[j] over one
    camera block, H stored feature-major ([d*d, CB] rows)."""
    xs = x_ref[:, :]
    if bf16_operands:
        xs = xs.astype(jnp.bfloat16)
    for i in range(d):
        acc = None
        for j in range(d):
            acc = _contract_rows(h_ref[i * d + j, :], xs, j, acc, acc_dt,
                                 bf16_operands)
        out_ref[i, :] = acc.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _grid_spec(tile, in_block, out_block, row_heights, d_in, d_out,
               n_tiles):
    in_specs = [
        pl.BlockSpec((1, tile), lambda i, ti, to, tf: (0, i)),
        pl.BlockSpec((1, tile), lambda i, ti, to, tf: (0, i)),
    ]
    for h in row_heights:
        in_specs.append(
            pl.BlockSpec((h, tile), lambda i, ti, to, tf: (0, i)))
    in_specs.append(
        pl.BlockSpec((d_in, in_block), lambda i, ti, to, tf: (0, ti[i])))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_in, tile_out, tile_first
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (d_out, out_block), lambda i, ti, to, tf: (0, to[i])),
    )


def _pad_table(table, num_blocks, block):
    pad = num_blocks * block - table.shape[1]
    return jnp.pad(table, ((0, 0), (0, pad))) if pad else table


@functools.partial(
    jax.jit,
    static_argnames=("tile", "in_block", "out_block", "num_in_blocks",
                     "num_out_blocks", "w_in_major", "bf16_operands",
                     "interpret", "name"))
def _fused_w_call(W, table, in_local, out_local, tile_in, tile_out,
                  tile_first, *, tile, in_block, out_block, num_in_blocks,
                  num_out_blocks, w_in_major, bf16_operands, interpret,
                  name="fused_coupling_w"):
    d_in = table.shape[0]
    d_out = W.shape[0] // d_in
    acc_dt = _acc_dtype(W.dtype, table.dtype)
    n_tiles = tile_in.shape[0]
    table_p = _pad_table(table, num_in_blocks, in_block)
    return pl.pallas_call(
        functools.partial(
            _fused_w_kernel, in_block=in_block, out_block=out_block,
            d_out=d_out, w_in_major=w_in_major,
            bf16_operands=bf16_operands, acc_dt=acc_dt),
        grid_spec=_grid_spec(tile, in_block, out_block, (W.shape[0],),
                             d_in, d_out, n_tiles),
        out_shape=kernel_out_shape(
            (d_out, num_out_blocks * out_block), acc_dt, in_local, W,
            table_p),
        interpret=interpret,
        name=name,
    )(tile_in, tile_out, tile_first, in_local, out_local, W, table_p)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "in_block", "out_block", "num_in_blocks",
                     "num_out_blocks", "bf16_operands", "interpret", "name"))
def _fused_j_call(Jin, Jout, table, in_local, out_local, tile_in, tile_out,
                  tile_first, *, tile, in_block, out_block, num_in_blocks,
                  num_out_blocks, bf16_operands, interpret,
                  name="fused_coupling_j"):
    d_in = table.shape[0]
    od = Jin.shape[0] // d_in
    d_out = Jout.shape[0] // od
    acc_dt = _acc_dtype(Jin.dtype, Jout.dtype, table.dtype)
    n_tiles = tile_in.shape[0]
    table_p = _pad_table(table, num_in_blocks, in_block)
    return pl.pallas_call(
        functools.partial(
            _fused_j_kernel, in_block=in_block, out_block=out_block,
            d_out=d_out, bf16_operands=bf16_operands, acc_dt=acc_dt),
        grid_spec=_grid_spec(tile, in_block, out_block,
                             (Jin.shape[0], Jout.shape[0]),
                             d_in, d_out, n_tiles),
        out_shape=kernel_out_shape(
            (d_out, num_out_blocks * out_block), acc_dt, in_local, Jin,
            Jout, table_p),
        interpret=interpret,
        name=name,
    )(tile_in, tile_out, tile_first, in_local, out_local, Jin, Jout,
      table_p)


# ---------------------------------------------------------------------------
# Public applies
# ---------------------------------------------------------------------------


def fused_coupling_apply(
    W_f: jax.Array,
    table: jax.Array,
    fplan: DeviceFusedPlan,
    w_in_major: bool,
    bf16_operands: bool = False,
    interpret: bool = False,
    name: str = "fused_coupling_w",
) -> jax.Array:
    """EXPLICIT fused matvec direction: [d_in, num_in] table ->
    [d_out, num_out_segments] sums.  `W_f` must already be in this
    direction's fused slot order (`permute_rows`).  `name` names the
    kernel (ops/segtiles.py, Pallas kernels)."""
    out = _fused_w_call(
        W_f, table, fplan.in_local, fplan.out_local, fplan.tile_in,
        fplan.tile_out, fplan.tile_first, tile=fplan.tile,
        in_block=fplan.in_block, out_block=fplan.out_block,
        num_in_blocks=fplan.num_in_blocks,
        num_out_blocks=fplan.num_out_blocks, w_in_major=w_in_major,
        bf16_operands=bf16_operands, interpret=interpret, name=name)
    return out[:, : fplan.num_out_segments]


def fused_coupling_apply_implicit(
    Jin_f: jax.Array,
    Jout_f: jax.Array,
    table: jax.Array,
    fplan: DeviceFusedPlan,
    bf16_operands: bool = False,
    interpret: bool = False,
    name: str = "fused_coupling_j",
) -> jax.Array:
    """IMPLICIT fused matvec direction (Jc/Jp rows pre-permuted)."""
    out = _fused_j_call(
        Jin_f, Jout_f, table, fplan.in_local, fplan.out_local,
        fplan.tile_in, fplan.tile_out, fplan.tile_first, tile=fplan.tile,
        in_block=fplan.in_block, out_block=fplan.out_block,
        num_in_blocks=fplan.num_in_blocks,
        num_out_blocks=fplan.num_out_blocks,
        bf16_operands=bf16_operands, interpret=interpret, name=name)
    return out[:, : fplan.num_out_segments]


def _pick_tile(n: int) -> int:
    for t in (FUSED_TILE, 256, 128):
        if n % t == 0:
            return t
    return n


def fused_single_block_apply(
    rows: jax.Array,
    table: jax.Array,
    in_local: jax.Array,
    out_local: jax.Array,
    out_block: int,
    w_in_major: bool = False,
    rows_out: Optional[jax.Array] = None,
    bf16_operands: bool = False,
    interpret: bool = False,
    name: str = "fused_ring_step",
) -> jax.Array:
    """Degenerate fused plan: ONE input block (the whole table) and ONE
    output block — the 2-D mesh's ring-step contraction, where the
    rotating point shard is the input block and the camera tile the
    output.  `rows` columns must be pre-masked (padding pairs zeroed).
    With `rows_out`, runs the implicit two-stage contraction."""
    n = in_local.shape[-1]
    tile = _pick_tile(int(n))
    n_tiles = n // tile
    tile_zero = jnp.zeros((n_tiles,), jnp.int32)
    tile_first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), jnp.zeros((n_tiles - 1,), jnp.int32)])
    in_l = in_local.reshape(1, -1).astype(jnp.int32)
    out_l = out_local.reshape(1, -1).astype(jnp.int32)
    if rows_out is None:
        return _fused_w_call(
            rows, table, in_l, out_l, tile_zero, tile_zero, tile_first,
            tile=tile, in_block=table.shape[1], out_block=out_block,
            num_in_blocks=1, num_out_blocks=1, w_in_major=w_in_major,
            bf16_operands=bf16_operands, interpret=interpret, name=name)
    return _fused_j_call(
        rows, rows_out, table, in_l, out_l, tile_zero, tile_zero,
        tile_first, tile=tile, in_block=table.shape[1],
        out_block=out_block, num_in_blocks=1, num_out_blocks=1,
        bf16_operands=bf16_operands, interpret=interpret, name=name)


# ---------------------------------------------------------------------------
# Fused block-diagonal M^-1 apply
# ---------------------------------------------------------------------------

FUSED_CAM_BLOCK = 512  # cameras per M^-1 apply grid step


@functools.partial(
    jax.jit,
    static_argnames=("cam_block", "bf16_operands", "interpret", "name"))
def _block_diag_call(Hrows, x, *, cam_block, bf16_operands, interpret,
                     name="fused_block_diag"):
    d = x.shape[0]
    nc = x.shape[1]
    nb = max(1, -(-nc // cam_block))
    acc_dt = _acc_dtype(Hrows.dtype, x.dtype)
    pad = nb * cam_block - nc
    if pad:
        Hrows = jnp.pad(Hrows, ((0, 0), (0, pad)))
        x = jnp.pad(x, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_block_diag_kernel, d=d,
                          bf16_operands=bf16_operands, acc_dt=acc_dt),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((d * d, cam_block), lambda i: (0, i)),
            pl.BlockSpec((d, cam_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((d, cam_block), lambda i: (0, i)),
        out_shape=kernel_out_shape((d, nb * cam_block), acc_dt, Hrows, x),
        interpret=interpret,
        name=name,
    )(Hrows, x)
    return out[:, :nc]


def block_diag_rows(Minv: jax.Array) -> jax.Array:
    """[Nc, d, d] inverted block diagonal -> feature-major [d*d, Nc]
    rows (row i*d+j holds M^-1[:, i, j]) — laid out ONCE per solve for
    the fused apply."""
    d = Minv.shape[-1]
    return jnp.transpose(Minv, (1, 2, 0)).reshape(d * d, Minv.shape[0])


def fused_block_diag_apply(
    Hrows: jax.Array,
    x: jax.Array,
    cam_block: int = FUSED_CAM_BLOCK,
    bf16_operands: bool = False,
    interpret: bool = False,
    name: str = "fused_block_diag",
) -> jax.Array:
    """Fused M^-1 apply: one kernel pass over camera blocks, f32 (f64)
    accumulation; the bf16 arm multiplies bf16 x bf16 and upcasts —
    value-for-value the cam_block_matvec(_bf16) einsum contract."""
    cam_block = min(cam_block, max(8, x.shape[1]))
    return _block_diag_call(
        Hrows, x, cam_block=cam_block, bf16_operands=bf16_operands,
        interpret=interpret, name=name).astype(x.dtype)


# ---------------------------------------------------------------------------
# XLA reference (parity oracle for tests; never the production fallback —
# with fused_kernels off the production path is the existing segtiles /
# XLA lowering, untouched)
# ---------------------------------------------------------------------------


def reference_coupling_apply(W, table, in_idx, out_idx, num_out,
                             w_in_major, d_in):
    """Plain gather/contract/scatter in XLA ops — the parity oracle."""
    pe = jnp.take(table, in_idx, axis=1, mode="clip")  # [d_in, nE]
    d_out = W.shape[0] // d_in
    te = jnp.stack([
        sum((W[(a * d_out + b) if w_in_major else (b * d_in + a)]
             .astype(pe.dtype) * pe[a])
            for a in range(d_in))
        for b in range(d_out)
    ])
    out = jnp.zeros((d_out, num_out), te.dtype)
    return out.at[:, out_idx].add(te, mode="drop")


def reference_coupling_apply_implicit(Jin, Jout, table, in_idx, out_idx,
                                      num_out, d_in):
    """Implicit two-stage contraction in XLA ops — the parity oracle of
    `fused_coupling_apply_implicit`."""
    pe = jnp.take(table, in_idx, axis=1, mode="clip")
    od = Jin.shape[0] // d_in
    d_out = Jout.shape[0] // od
    u = jnp.stack([
        sum(Jin[o * d_in + a].astype(pe.dtype) * pe[a] for a in range(d_in))
        for o in range(od)])
    te = jnp.stack([
        sum(Jout[o * d_out + b].astype(u.dtype) * u[o] for o in range(od))
        for b in range(d_out)])
    out = jnp.zeros((d_out, num_out), te.dtype)
    return out.at[:, out_idx].add(te, mode="drop")


def check_kernels(interpret: bool = False):
    """Run the fused kernels at the production geometry (FUSED_TILE,
    FUSED_BLOCK_CAM, FUSED_BLOCK_PT, FUSED_CAM_BLOCK) against their XLA
    references; returns [(kernel, geometry, rel_err)] and raises
    segtiles.KernelMismatch listing them all when any disagrees.

    Covers the explicit apply in both matvec directions (cam->pt with
    in-major W rows, pt->cam with out-major), the implicit apply in
    both directions, and the block-diagonal M^-1 apply, all f32 as the
    solve runs them.  Several blocks per side and several tiles per
    bucket, so the accumulate branches run.
    """
    from megba_tpu.ops.segtiles import rel_err, require_all_close

    rng = np.random.default_rng(0)
    nc, npt, ne, od = 600, 3000, 8 * FUSED_TILE * 3, 2
    cam_idx = rng.integers(0, nc, ne).astype(np.int32)
    pt_idx = rng.integers(0, npt, ne).astype(np.int32)
    mask = (rng.random(ne) > 0.1).astype(np.float32)
    _, _, to_pt, to_cam = build_fused_dual_plans(
        cam_idx, pt_idx, mask, nc, npt)
    m = jnp.asarray(mask)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    W = normal(9 * 3, ne) * m
    Jc = normal(od * 9, ne) * m
    Jp = normal(od * 3, ne) * m
    p_cam, q_pt = normal(9, nc), normal(3, npt)
    Minv, x = normal(nc, 9, 9), normal(9, nc)
    geometry = (f"tile={FUSED_TILE} block_cam={FUSED_BLOCK_CAM} "
                f"block_pt={FUSED_BLOCK_PT}")
    pairs = (
        ("fused_coupling_apply cam->pt",
         fused_coupling_apply(permute_rows(W, to_pt), p_cam, to_pt,
                              w_in_major=True, interpret=interpret),
         reference_coupling_apply(W, p_cam, cam_idx, pt_idx, npt, True, 9)),
        ("fused_coupling_apply pt->cam",
         fused_coupling_apply(permute_rows(W, to_cam), q_pt, to_cam,
                              w_in_major=False, interpret=interpret),
         reference_coupling_apply(W, q_pt, pt_idx, cam_idx, nc, False, 3)),
        ("fused_coupling_apply_implicit cam->pt",
         fused_coupling_apply_implicit(
             permute_rows(Jc, to_pt), permute_rows(Jp, to_pt), p_cam,
             to_pt, interpret=interpret),
         reference_coupling_apply_implicit(
             Jc, Jp, p_cam, cam_idx, pt_idx, npt, 9)),
        ("fused_coupling_apply_implicit pt->cam",
         fused_coupling_apply_implicit(
             permute_rows(Jp, to_cam), permute_rows(Jc, to_cam), q_pt,
             to_cam, interpret=interpret),
         reference_coupling_apply_implicit(
             Jp, Jc, q_pt, pt_idx, cam_idx, nc, 3)),
        (f"fused_block_diag_apply cam_block={FUSED_CAM_BLOCK}",
         fused_block_diag_apply(block_diag_rows(Minv), x,
                                interpret=interpret),
         jnp.einsum("cij,jc->ic", Minv, x)),
    )
    out = [(name, geometry, rel_err(got, want)) for name, got, want in pairs]
    require_all_close(out)
    return out
