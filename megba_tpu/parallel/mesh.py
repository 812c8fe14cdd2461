"""Device mesh + edge-axis sharding.

The TPU-native replacement for the reference's entire distribution
machinery: the host-side `for (i < worldSize) cudaSetDevice(i)` loops
(reference src/edge/base_edge.cu:20-25, src/solver/schur_pcg_solver.cu:
164-197), the per-device contiguous edge partition
(MemoryPool::getItemNum, memory_pool.h:48-63; base_problem.cpp:59-74) and
the NCCL allreduce set (SURVEY.md §2.3) become: a 1-D
`jax.sharding.Mesh` over axis "edges", `jax.shard_map` with edge arrays
split on their leading axis (the same contiguous partition, but
equal-size via padding), and `jax.lax.psum` inside the jitted solve.

Unlike the reference (single-process, single-node, ncclCommInitAll —
handle_manager.cpp:17-22), the same code runs multi-host: under
`jax.distributed`, the Mesh spans all hosts' devices, XLA routes the
psums over ICI within a slice and DCN across slices, and nothing here
changes.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from megba_tpu.algo.lm import LMResult, lm_solve
from megba_tpu.analysis.retrace import static_key, traced
from megba_tpu.common import ProblemOption, strip_observability
from megba_tpu.core.types import pad_edges

EDGE_AXIS = "edges"
# Second mesh axis of the 2-D distribution (make_mesh_2d): camera
# blocks.  Under a 2-D mesh the edge axis splits over BOTH axes
# (P((EDGE_AXIS, CAM_AXIS))), cameras tile over CAM_AXIS, and the Schur
# matvec's reductions become subgroup-scoped (solver/pcg.make_matvec_2d)
# instead of world-wide.
CAM_AXIS = "cams"


def collective_payload_cast(enabled: bool, compute_dtype=None):
    """(down, up) casts around IN-BODY collective payloads.

    The bf16-collective half of the bf16 MXU pipeline
    (SolverOption.bf16_collectives): `down` casts a partial-sum payload
    to bfloat16 just before it goes on the wire, `up` restores the f32
    compute dtype on the reduced result — halving the bytes every
    in-body psum / psum_scatter / ppermute / all_gather moves, the
    `collective_bytes_per_sp` budget axis.  With `enabled=False` both
    are identity functions that emit NO ops, so every existing program
    lowers byte-identically.

    The cross-shard reduction itself then runs on bf16 values (the
    payload is summed as transmitted); the once-per-solve reductions
    (Schur build, reduced RHS, coarse builds, back-substitution) never
    ride this cast — solver/pcg.py scopes it to the S·p matvec the PCG
    while body dispatches.

    Probed hazard (jaxlib 0.4.36, XLA:CPU): the CPU backend's float
    normalization pass promotes bf16 collectives back to f32 in the
    compiled executable (the convert pair is fused across the
    all-reduce), so on the CPU lane the wire payload this cast DECLARES
    is not the payload that moves — the HLO auditor therefore prices
    the declared (StableHLO) payload and pins it structurally
    (analysis/program_audit.py), which is what a TPU lowering (native
    bf16 collectives) executes.
    """
    if not enabled:
        ident = _payload_identity
        return ident, ident
    cd = jnp.float32 if compute_dtype is None else compute_dtype

    def down(x):
        return x.astype(jnp.bfloat16)

    def up(x):
        return x.astype(cd)

    return down, up


def _payload_identity(x):
    return x


def mesh_axes(mesh: Mesh):
    """The lm_solve `axis_name` for this mesh: the single edge axis for
    the 1-D mesh (every historical program, byte-identical), the
    (edge, camera) tuple for the 2-D mesh — `jax.lax.psum` over the
    tuple reduces over the whole world, so every existing psum site
    (cost sums, Schur build, coarse builds) is correct on both meshes
    without change."""
    names = tuple(mesh.axis_names)
    return names if len(names) > 1 else names[0]


def factor_mesh_2d(world_size: int, cam_blocks: int = 0):
    """Resolve (edge_shards, cam_blocks) for a 2-D mesh of `world_size`
    devices.

    `cam_blocks > 0` must divide world_size (validate_options enforces
    the same contract); 0 selects the largest divisor <=
    sqrt(world_size) — the square-ish factorisation that keeps BOTH
    subgroups small (a 1 x W or W x 1 degenerate mesh reproduces the
    1-D communication pattern on one of the two stages).
    """
    world_size = int(world_size)
    if world_size < 1:
        raise ValueError(f"world_size must be >= 1, got {world_size}")
    cam_blocks = int(cam_blocks)
    if cam_blocks > 0:
        if world_size % cam_blocks or cam_blocks > world_size:
            raise ValueError(
                f"cam_blocks={cam_blocks} does not factor "
                f"world_size={world_size} into edge_shards x cam_blocks")
        return world_size // cam_blocks, cam_blocks
    c = 1
    d = 1
    while d * d <= world_size:
        if world_size % d == 0:
            c = d
        d += 1
    return world_size // c, c


def nearest_cam_blocks(world_size: int, cam_blocks: int) -> int:
    """Largest feasible cam_blocks <= the requested one for this world.

    The elastic shrink-world resume (robustness/elastic.resume_elastic)
    uses this to re-factor a 2-D solve onto a SMALLER 2-D mesh: the
    surviving world keeps as much of the camera-block split as it can
    still factor (degrading to 1 — the 1-D layout — only when the new
    world size shares no divisor with the old camera split).
    """
    world_size = int(world_size)
    cam_blocks = max(1, int(cam_blocks))
    best = 1
    for c in range(1, min(cam_blocks, world_size) + 1):
        if world_size % c == 0:
            best = c
    return best


def make_mesh_2d(
    edge_shards: int,
    cam_blocks: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the 2-D (edge_shards x cam_blocks) mesh.

    Axis order is (EDGE_AXIS, CAM_AXIS): a P((EDGE_AXIS, CAM_AXIS))
    edge split hands device (e, c) the contiguous block e*C + c —
    exactly the block order `ops.segtiles.build_camera_tile_plan` lays
    the padded edge stream out in.  Device sourcing matches `make_mesh`
    (local_devices_only scope; never another platform's devices).
    """
    E, C = int(edge_shards), int(cam_blocks)
    if E < 1 or C < 1:
        raise ValueError(
            f"edge_shards and cam_blocks must be >= 1, got {E} x {C}")
    world = E * C
    if devices is None:
        base = make_mesh(world, devices)
        devices = list(base.devices.reshape(-1))
    if len(devices) < world:
        raise ValueError(
            f"2-D mesh {E}x{C} needs {world} devices, have {len(devices)}")
    grid = np.asarray(list(devices)[:world]).reshape(E, C)
    return Mesh(grid, (EDGE_AXIS, CAM_AXIS))

# Elastic shrink-world scope (parallel/multihost + robustness/elastic):
# after peers are lost/abandoned, `jax.devices()` STILL lists the dead
# processes' devices — a mesh (or default-device dispatch) touching one
# would address a process that no longer exists.  While this scope is
# active, `make_mesh` draws only from devices THIS process owns.  A
# process-global count, not a thread-local: elastic dispatches run on
# watchdog worker threads, and a dead world is dead for every thread.
_LOCAL_ONLY_DEPTH = 0
_LOCAL_ONLY_LOCK = threading.Lock()


@contextlib.contextmanager
def local_devices_only():
    """Context manager scoping `make_mesh` to this process's devices.

    The shrink-world resume path (`robustness.elastic.resume_elastic`)
    wraps the re-lowered solve in this so the smaller mesh is built
    from surviving local devices regardless of what the stale global
    device list claims.  Re-entrant; affects all threads (see above).
    """
    global _LOCAL_ONLY_DEPTH
    with _LOCAL_ONLY_LOCK:
        _LOCAL_ONLY_DEPTH += 1
    try:
        yield
    finally:
        with _LOCAL_ONLY_LOCK:
            _LOCAL_ONLY_DEPTH -= 1


def local_only_active() -> bool:
    return _LOCAL_ONLY_DEPTH > 0


def _this_process_devices(devices):
    pi = jax.process_index()
    return [d for d in devices if d.process_index == pi]


def make_mesh(
    world_size: int, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the 1-D edge-sharding mesh.

    `world_size` plays the role of the reference's ProblemOption::deviceUsed
    GPU count (common.h:47, validated against the device count at
    memory_pool.cu:50-56).  Under `local_devices_only()` (elastic
    shrink-world resume) the default device pool is restricted to this
    process's own devices; an explicit `devices=` list is always taken
    as-is — the caller owns it.
    """
    if devices is None:
        devices = jax.devices()
        if local_only_active():
            devices = _this_process_devices(devices)
    if world_size > len(devices):
        raise ValueError(
            f"world_size {world_size} exceeds the {len(devices)} available "
            f"{devices[0].platform if devices else ''} device(s); a solve "
            "never moves to another platform's devices")
    return Mesh(np.asarray(devices[:world_size]), (EDGE_AXIS,))


def shard_edge_arrays(
    obs: np.ndarray,
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    world_size: int,
    dtype=None,
):
    """Pad the edge axis to a multiple of world_size; returns (+mask).

    The mask dtype follows `obs` unless overridden, so a float32 problem
    is never silently upcast by a float64 mask.
    """
    if dtype is None:
        dtype = obs.dtype
    return pad_edges(obs, cam_idx, pt_idx, world_size, dtype=dtype)


def distributed_lm_solve(
    residual_jac_fn,
    cameras: jax.Array,
    points: jax.Array,
    obs: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    mask: jax.Array,
    option: ProblemOption,
    mesh: Mesh,
    sqrt_info: Optional[jax.Array] = None,
    cam_fixed: Optional[jax.Array] = None,
    pt_fixed: Optional[jax.Array] = None,
    verbose: bool = False,
    cam_sorted: bool = False,
    plans=None,
    initial_region=None,
    initial_v=None,
    initial_dx=None,
    fault_plan=None,
    cluster_plan=None,
    tile_plan=None,
    jit_cache: Optional[dict] = None,
    donate: bool = False,
    lower_only: bool = False,
    timer=None,
) -> LMResult:
    """Run the full LM solve SPMD over the mesh's edge axis.

    Parameter state (cameras/points, Hessian diagonals, PCG vectors) is
    replicated — the reference's layout exactly (base_problem.cu:21-29,
    base_linear_system.h:33-34) — while every per-edge array lives only on
    its shard.  The entire LM loop, PCG included, is ONE jitted SPMD
    program; per-iteration synchronisation is the psum set documented in
    builder.py/pcg.py.

    DONATION CONTRACT: with `donate=True`, `cameras` and `points` are
    donated — the result's parameter arrays alias their buffers, and
    device arrays passed here are DELETED by the call.  The default is
    False on this PUBLIC entry point so a caller that reuses its device
    arrays after the call keeps its previously-valid usage; the internal
    flat_solve path opts in (it materializes fresh host operands per
    call and never reads them after the solve, so donation is free
    parameter-memory savings there).  Under a multi-process mesh every
    operand is lifted into a global array first
    (parallel/multihost.globalize_for_mesh), so host values are required
    there anyway.

    `lower_only=True` returns the `jax.stages.Lowered` of the exact SPMD
    program this call would dispatch (auditor hook,
    analysis/program_audit.py; single-process only).

    `timer` (utils.timing.PhaseTimer, flat_solve's) times the
    sub-phase dispatch.call: the jitted call, the host operands'
    transfer included.
    """
    n_edge = obs.shape[-1]
    if n_edge % mesh.devices.size != 0:
        raise ValueError(
            f"edge count {n_edge} not divisible by mesh size "
            f"{mesh.devices.size}; pad with shard_edge_arrays first"
        )

    # Program-identity surface: _cached_sharded_solve and the
    # caller-owned jit_cache both key on `option`, so strip the
    # observability sinks (common.OBSERVABILITY_FIELDS) on this PUBLIC
    # entry — the internal flat_solve path arrives pre-stripped
    # (identity pass-through, same cache slots), but a direct caller
    # with a telemetry/metrics-armed option previously split the
    # program cache (the identity lane's key-surface-drift finding,
    # fixed at the source).
    option = strip_observability(option)

    # Feature-major edge arrays [F, nE] split on the MINOR axis; 1-D
    # index/mask arrays on their only axis; parameters replicated.
    # Under the 2-D mesh the edge axis splits over BOTH mesh axes
    # (edge-shard-major device blocks — the camera-tile plan laid the
    # stream out in exactly this order) and the matvec operand
    # (tile_plan) follows the same split.
    is_2d = len(mesh.axis_names) > 1
    split = (EDGE_AXIS, CAM_AXIS) if is_2d else EDGE_AXIS
    edge = P(None, split)
    edge1d = P(split)
    rep = P()
    if is_2d and plans is not None:
        raise ValueError(
            "the 2-D mesh path does not compose with the Pallas tiled "
            "plans (DualPlans); lower with use_tiled=False")
    if is_2d and tile_plan is None:
        raise ValueError(
            "a 2-D mesh solve needs the camera-tile plan operand: solve "
            "through flat_solve (which plans + caches it) or pass "
            "tile_plan=ops.segtiles.device_camera_tile_plan(...)")

    # Optional operands can't be None inside shard_map specs; pass the
    # present ones positionally with matching specs.
    dtype = cameras.dtype
    ir = option.algo_option.initial_region if initial_region is None else initial_region
    iv = 2.0 if initial_v is None else initial_v
    from megba_tpu.observability.emit import next_verbose_token

    args = [cameras, points, obs, cam_idx, pt_idx, mask,
            jnp.asarray(ir, dtype), jnp.asarray(iv, dtype),
            jnp.asarray(next_verbose_token(), jnp.int32)]
    in_specs = [rep, rep, edge, edge1d, edge1d, edge1d, rep, rep, rep]
    optional = [
        ("sqrt_info", sqrt_info, edge),
        ("cam_fixed", cam_fixed, rep),
        ("pt_fixed", pt_fixed, rep),
        # Warm-start resume state ([cd, Nc] rows): replicated like the
        # parameter blocks; the in-loop warm-start carry it seeds stays
        # replicated too (it is the PCG's psum-derived output), so the
        # solver's out_specs=P() contract is unchanged.
        ("initial_dx", initial_dx, rep),
        # Per-shard tiled plans: every leaf carries a leading shard axis
        # split by the mesh (ops/segtiles.make_sharded_dual_plans).
        ("plans", plans, P(EDGE_AXIS)),
    ]
    if fault_plan is not None:
        # Seeded-fault operand (robustness/faults.py): the edge poison
        # is shard-local like every other edge array; the window/offset
        # scalars and the point mask ride replicated.
        from megba_tpu.robustness.faults import fault_partition_specs

        optional.append(("fault_plan", fault_plan,
                         fault_partition_specs(edge_spec=edge1d)))
    if cluster_plan is not None:
        # Coarse-space plan (ops/segtiles.py; two-level OR multilevel):
        # the per-edge pc_slot stream follows the edge shards, the
        # cluster/incidence/pair/assignment tables ride replicated (the
        # coarse assembly after the V psum — and every dense hierarchy
        # level above it — is identical tiny work per shard).
        from megba_tpu.ops.segtiles import coarse_plan_partition_specs

        optional.append(("cluster_plan", cluster_plan,
                         coarse_plan_partition_specs(cluster_plan,
                                                     edge_spec=edge1d)))
    if tile_plan is not None:
        # 2-D matvec operand: the per-edge cam_local stream and the
        # per-device point-shard bucket tables follow the 2-D edge
        # split (ops/segtiles.tile_plan_partition_specs).
        from megba_tpu.ops.segtiles import tile_plan_partition_specs

        optional.append(("tile_plan", tile_plan,
                         tile_plan_partition_specs(tile_plan, edge1d)))
    keys = tuple(k for k, v, _ in optional if v is not None)
    args += [v for _, v, _ in optional if v is not None]
    in_specs += [spec for _, v, spec in optional if v is not None]

    jitted = get_or_build_program(
        jit_cache, _cached_sharded_solve, _build_sharded_solve,
        residual_jac_fn, mesh, option, keys, tuple(in_specs), verbose,
        cam_sorted, donate)

    if lower_only:
        # Auditor hook (analysis/program_audit.py): hand back the
        # Lowered of the exact SPMD program this call would dispatch.
        # Single-process only — the audit never globalizes operands.
        return jitted.lower(*args)

    from megba_tpu.parallel.multihost import dispatch_on_mesh

    # The jitted call transfers the host operands itself: placing them on
    # the mesh first raised every chip's peak HBM by 34 MB on BAL final
    # over a four-chip TPU v5e host.
    with (timer.phase("dispatch.call") if timer is not None
          else contextlib.nullcontext()):
        return dispatch_on_mesh(jitted, mesh, args, in_specs)


def get_or_build_program(jit_cache, cached_fn, build_fn, engine, *cfg):
    """Fetch/compile a jitted solve program.

    `jit_cache is None` -> the global lru (`cached_fn`) for long-lived
    engines.  Otherwise the caller-owned dict, keyed by the FULL builder
    argument list `(engine, *cfg)` — the key is exactly what `build_fn`
    receives, so it cannot drift out of sync with the configuration and
    serve a program compiled for different options (and a shared dict can
    never return a program compiled for a different engine).  Used by both
    solve.flat_solve and distributed_lm_solve; per-problem closure engines
    go through the dict path so their programs die with the problem.
    """
    if jit_cache is None:
        return cached_fn(engine, *cfg)
    key = (engine, *cfg)
    prog = jit_cache.get(key)
    if prog is None:
        prog = jit_cache[key] = build_fn(engine, *cfg)
    return prog


def _build_sharded_solve(residual_jac_fn, mesh, option, keys, in_specs, verbose,
                         cam_sorted=False, donate=False):
    """Build the jitted shard_map'ed solve (uncached)."""

    axes = mesh_axes(mesh)

    def fn(cameras, points, obs, cam_idx, pt_idx, mask, init_region, init_v,
           verbose_token, *extras):
        kwargs = dict(zip(keys, extras))
        if "plans" in kwargs:
            # Leaves arrive with a singleton shard axis; drop it so the
            # body sees this shard's own plan.
            from megba_tpu.ops.segtiles import squeeze_plans

            kwargs["plans"] = squeeze_plans(kwargs["plans"])
        return lm_solve(
            residual_jac_fn, cameras, points, obs, cam_idx, pt_idx, mask,
            option, axis_name=axes, verbose=verbose, cam_sorted=cam_sorted,
            initial_region=init_region,
            initial_v=init_v, verbose_token=verbose_token,
            **kwargs)

    # `traced`: retrace sentinel hook (analysis/retrace.py) — one count
    # per compilation of this SPMD program; zero cost once compiled.
    # The static world tag carries the mesh SHAPE, not just its size: a
    # 4-device 1-D mesh and a 2x2 2-D mesh are different programs.
    world_tag = "world" + "x".join(str(n) for n in mesh.devices.shape)
    fn = traced(
        "mesh.sharded", fn,
        static=static_key(residual_jac_fn, world_tag,
                          option, keys, verbose, cam_sorted, donate))
    # The Pallas interpreter carries no varying-axes types through a
    # kernel body (jax 0.9 raises on its iota compares), so only a
    # program that interprets the fused kernels (off the TPU, the CPU
    # parity lane) skips the check; compiled with Mosaic they keep it.
    from megba_tpu.ops.fused import kernels_supported

    interpreted = (option.solver_option.fused_kernels
                   and not kernels_supported())
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P(), check_vma=not interpreted)
    # Donate the replicated parameter blocks only when the caller opted
    # in (the internal flat_solve path does; the public entry point
    # defaults to donate=False so external device arrays survive the
    # call).
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# Global program cache for long-lived engines.  jax.jit caches by callable
# identity, so rebuilding the closure every call would recompile the full
# LM+PCG program per solve; caching on (engine fn, mesh, option, operand
# layout) pays tracing + compilation once per configuration.  ProblemOption
# is frozen/hashable for exactly this purpose.  Per-problem closure engines
# use the caller-owned jit_cache path above instead.
_cached_sharded_solve = functools.lru_cache(maxsize=64)(_build_sharded_solve)
