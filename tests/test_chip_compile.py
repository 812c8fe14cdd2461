"""Compile the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test compiles one kernel at the production geometry
and venice width for a v5e described by `get_topology_desc`, with the
TPU compiler installed here, and asserts that Mosaic emitted it as a
`tpu_custom_call`.  This catches what the Pallas interpreter cannot —
tiling, VMEM and block-shape refusals — at no chip time.  A compile
that passes is not a chip run; `python chip_smoke.py` is.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the driver
imports every test file in each of its workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from megba_tpu.ops import fused, segtiles

# BAL venice: 1778 cameras, 993,923 points; 5,013,504 plan slots is the
# padded edge stream of its 5,001,946 observations (a whole number of
# tiles at both production tile sizes).
VENICE_CAMERAS = 1778
VENICE_POINTS = 993_923
VENICE_SLOTS = 5_013_504
OD = 2  # BAL residual rows

GEOMETRIES = {
    # side: (tile, block, segments, vertex dim, one-hot window).  The
    # camera plan's window is its block; venice's point plan builds
    # 384-wide windows (a tile spans 206 ids), and a point plan whose
    # tiles span more takes its whole block.
    "cam": (segtiles.DEFAULT_TILE_CAM, segtiles.DEFAULT_BLOCK_CAM,
            VENICE_CAMERAS, 9, segtiles.DEFAULT_BLOCK_CAM),
    "pt": (segtiles.DEFAULT_TILE_PT, segtiles.DEFAULT_BLOCK_PT,
           VENICE_POINTS, 3, segtiles.DEFAULT_BLOCK_PT),
    "pt_window384": (segtiles.DEFAULT_TILE_PT, segtiles.DEFAULT_BLOCK_PT,
                     VENICE_POINTS, 3, 384),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_config():
    """The chip's configuration around the compiles.  x64 off, as on the
    chip (the tests' conftest turns it on; Mosaic refuses the i64 block
    indices it would produce).  The persistent cache off: a compile for
    a described chip is written to it but cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    cc.reset_cache()


@pytest.fixture
def compile_on(one_chip, chip_config):
    def run(jitted, *shapes, **static):
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in shapes]
        text = jitted.lower(*specs, **static).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        return text
    return run


def _slot_arrays(tile, block, segments, window):
    """The plan operands' shapes: (local, per-tile array, static
    arguments)."""
    n_tiles = VENICE_SLOTS // tile
    num_blocks = -(-segments // block)
    static = dict(tile=tile, block=block, num_blocks=num_blocks,
                  interpret=False, window=window)
    local = ((1, VENICE_SLOTS), jnp.int32)
    tiles = ((n_tiles,), jnp.int32)
    return local, tiles, static


def _rows(n):
    return ((n, VENICE_SLOTS), jnp.float32)


@pytest.mark.parametrize("side", sorted(GEOMETRIES))
def test_tile_reduce_compiles(compile_on, side):
    tile, block, ns, d, window = GEOMETRIES[side]
    local, tiles, static = _slot_arrays(tile, block, ns, window)
    compile_on(segtiles._tile_reduce_call, _rows(d), local, tiles, tiles,
               tiles, **static)


@pytest.mark.parametrize("side", sorted(GEOMETRIES))
def test_tile_expand_compiles(compile_on, side):
    tile, block, ns, d, window = GEOMETRIES[side]
    local, tiles, static = _slot_arrays(tile, block, ns, window)
    compile_on(segtiles._tile_expand_call, ((d, ns), jnp.float32), local,
               tiles, tiles, **static)


@pytest.mark.parametrize("side", sorted(GEOMETRIES))
def test_jtj_grad_reduce_compiles(compile_on, side):
    tile, block, ns, d, window = GEOMETRIES[side]
    local, tiles, static = _slot_arrays(tile, block, ns, window)
    compile_on(segtiles._jtj_reduce_call, _rows(OD * d), _rows(OD), local,
               tiles, tiles, tiles, d=d, od=OD, **static)


@pytest.mark.parametrize("side", sorted(GEOMETRIES))
def test_coupling_expand_compiles(compile_on, side):
    tile, block, ns, d, window = GEOMETRIES[side]
    local, tiles, static = _slot_arrays(tile, block, ns, window)
    compile_on(segtiles._expand_matvec_call, _rows(OD * d),
               ((d, ns), jnp.float32), local, tiles, tiles, d=d, **static)


@pytest.mark.parametrize("side", sorted(GEOMETRIES))
def test_coupling_reduce_compiles(compile_on, side):
    tile, block, ns, d, window = GEOMETRIES[side]
    local, tiles, static = _slot_arrays(tile, block, ns, window)
    compile_on(segtiles._matvec_reduce_call, _rows(OD * d), _rows(OD),
               local, tiles, tiles, tiles, d=d, **static)


# Fused kernels: (in segments, in block, in dim) -> (out ...), per matvec
# direction, at FUSED_TILE over the venice slot stream.
DIRECTIONS = {
    "cam->pt": (VENICE_CAMERAS, fused.FUSED_BLOCK_CAM, 9,
                VENICE_POINTS, fused.FUSED_BLOCK_PT, 3),
    "pt->cam": (VENICE_POINTS, fused.FUSED_BLOCK_PT, 3,
                VENICE_CAMERAS, fused.FUSED_BLOCK_CAM, 9),
}


def _fused_arrays(direction):
    n_in, in_block, d_in, n_out, out_block, d_out = DIRECTIONS[direction]
    static = dict(tile=fused.FUSED_TILE, in_block=in_block,
                  out_block=out_block,
                  num_in_blocks=-(-n_in // in_block),
                  num_out_blocks=-(-n_out // out_block),
                  bf16_operands=False, interpret=False)
    tiles = ((VENICE_SLOTS // fused.FUSED_TILE,), jnp.int32)
    local = ((1, VENICE_SLOTS), jnp.int32)
    table = ((d_in, n_in), jnp.float32)
    plan = (tiles, tiles, tiles, local, local)
    return d_in, d_out, table, plan, static


@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
def test_fused_coupling_apply_compiles(compile_on, direction):
    d_in, d_out, table, plan, static = _fused_arrays(direction)
    # cam->pt reads the coupling rows in-major, pt->cam out-major: the
    # two layouts the explicit solve hands the kernel.
    compile_on(fused._fused_w_call, _rows(d_in * d_out), table, plan[3],
               plan[4], *plan[:3], w_in_major=direction == "cam->pt",
               **static)


@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
def test_fused_coupling_apply_implicit_compiles(compile_on, direction):
    d_in, d_out, table, plan, static = _fused_arrays(direction)
    compile_on(fused._fused_j_call, _rows(OD * d_in), _rows(OD * d_out),
               table, plan[3], plan[4], *plan[:3], **static)


def test_fused_block_diag_apply_compiles(compile_on):
    compile_on(fused._block_diag_call,
               ((81, VENICE_CAMERAS), jnp.float32),
               ((9, VENICE_CAMERAS), jnp.float32),
               cam_block=fused.FUSED_CAM_BLOCK, bf16_operands=False,
               interpret=False)
