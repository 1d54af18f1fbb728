"""The main-path kernels compile for a TPU v5e at the paper's sizes.

Nothing here runs on a chip: each test compiles for a described (not
attached) ``v5e`` topology, so what the TPU compiler refuses — tiling of
blocks, VMEM over the scoped limit, primitives Mosaic cannot lower — fails
here at no chip time.  Shapes are those of the cubby scene at 524,288
points, depth 7, against the paper's Table III batch of 10,500 link OBBs,
and of MPiNets' set abstractions at its published widths over 8 problems
(6,272 points each, 512 then 128 centres, 128 neighbours).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers only the
worker that runs this file may do so.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.octree import META_ROW_ALIGN
from repro.core.quantize import META_FORMAT_WORDS
from repro.kernels.ballquery.kernel import make_ball_query_call
from repro.kernels.ballquery.ops import MAX_ROWS
from repro.kernels.compact.ops import stream_compact
from repro.kernels.fps.kernel import make_fps_call
from repro.kernels.persist.kernel import RESIDENT_WINDOW, make_persist_call
from repro.kernels.persist.ops import (DEFAULT_VMEM_BUDGET, MAX_TILE_BQ,
                                       MAX_TILE_FRONTIER, choose_meta_layout,
                                       kernel_vmem_limit, meta_table_bytes,
                                       sub_window_rows)
from repro.kernels.traverse.kernel import LANES, make_traverse_call

#: Level widths of ``make_scene("cubby", 0, 524288)`` at depth 7.
CUBBY_D7_WIDTHS = (1, 4, 32, 168, 996, 5664, 27570, 114094)
DEPTH = len(CUBBY_D7_WIDTHS) - 1
N_MAX = max(CUBBY_D7_WIDTHS)
QUERIES = 25 * 60 * 7
#: The engine's first frontier bucket for 10,500 queries.
CAPACITY = 16384
BQ = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it.
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


#: What the chip benchmark's trace reduction matches a kernel's device
#: events by.
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def _assert_named_kernel(text: str, name: str) -> None:
    """The compiled program holds a Pallas custom call named ``name``."""
    calls = [l for l in text.splitlines() if TPU_CUSTOM_CALL in l]
    assert calls, "no tpu_custom_call in the compiled program"
    assert any(f"%{name}" in l for l in calls), (name, calls[0][:200])


def test_chooser_layout_at_paper_scale():
    """The cubby table (14 MiB of fp32 rows) fits the v5e-derived resident
    budget, so the chooser keeps it resident and uncompressed."""
    assert tuple(choose_meta_layout(DEPTH, N_MAX)) == ("resident", "fp32")


def _persist_text(one_chip, layout, fmt, n_max, bq, fcap) -> str:
    """Compiled text of the megakernel over the Table III batch in tiles of
    ``bq`` queries, a ``fcap``-lane frontier and an ``n_max``-wide table."""
    stream = layout == "streamed"
    L = DEPTH + 1
    tiles = -(-QUERIES // bq)
    n_rows = -(-n_max // RESIDENT_WINDOW) * RESIDENT_WINDOW
    vpf = META_FORMAT_WORDS[fmt]
    call = make_persist_call(
        tiles, bq, fcap, DEPTH, n_rows, False, False, stream, fmt,
        sub_window_rows(n_max),
        kernel_vmem_limit(DEPTH, n_max, fmt, stream, bq, fcap))
    return _compiled_text(
        call, _shape(one_chip, (3 + L,), jnp.float32),
        _shape(one_chip, (L,)), _shape(one_chip, (L,)),
        _shape(one_chip, (tiles,)), _shape(one_chip, (1,)),
        _shape(one_chip, (tiles * bq, 15), jnp.float32),
        _shape(one_chip, (tiles * bq, 2)),
        _shape(one_chip, (L, vpf, n_rows // META_ROW_ALIGN, META_ROW_ALIGN)))


@pytest.mark.parametrize("layout,fmt", [
    ("resident", "fp32"), ("resident", "bf16"), ("resident", "u8"),
    ("streamed", "fp32"), ("streamed", "bf16"), ("streamed", "u8")])
def test_persist_megakernel_compiles(one_chip, layout, fmt):
    text = _persist_text(one_chip, layout, fmt, N_MAX, BQ, CAPACITY)
    _assert_named_kernel(text, "persist_traverse")


def test_persist_megakernel_compiles_at_vmem_budget_edge(one_chip):
    """The largest tile (MAX_TILE_BQ queries, MAX_TILE_FRONTIER lanes)
    over a resident fp32 table as wide as DEFAULT_VMEM_BUDGET allows: the
    corner the chooser may pick resident must fit the scoped limit."""
    rows = DEFAULT_VMEM_BUDGET // meta_table_bytes(DEPTH, RESIDENT_WINDOW)
    n_max = rows * RESIDENT_WINDOW
    assert meta_table_bytes(DEPTH, n_max) <= DEFAULT_VMEM_BUDGET
    assert tuple(choose_meta_layout(DEPTH, n_max)) == ("resident", "fp32")
    text = _persist_text(one_chip, "resident", "fp32", n_max, MAX_TILE_BQ,
                         MAX_TILE_FRONTIER)
    _assert_named_kernel(text, "persist_traverse")


def test_traverse_step_compiles(one_chip):
    call = make_traverse_call(CAPACITY, 1024, False, False)
    rows = CAPACITY // LANES
    text = _compiled_text(
        call, _shape(one_chip, (2,)), _shape(one_chip, (4,), jnp.float32),
        _shape(one_chip, (15, rows, LANES), jnp.float32),
        _shape(one_chip, (2, rows, LANES)))
    _assert_named_kernel(text, "traverse_step")


def test_stream_compaction_compiles(one_chip):
    """Compaction of a level's 8x-expanded candidates (an XLA scan and
    scatter) at the engine's frontier capacity."""
    _compiled_text(lambda m, v: stream_compact(m, v, CAPACITY),
                   _shape(one_chip, (8 * CAPACITY,), jnp.bool_),
                   _shape(one_chip, (8 * CAPACITY, 2)))


#: MPiNets' planning batch: problems, points, and (centres, radius) of its
#: two set abstractions, whose input clouds are 6,272 and 512 points.
PROBLEMS = 8
MPINETS_SA = ((6272, 512, 0.05), (512, 128, 0.3))


@pytest.mark.parametrize("n,m", [(n, m) for n, m, _ in MPINETS_SA],
                         ids=["sa1", "sa2"])
def test_fps_sample_compiles(one_chip, n, m):
    """Every pick of a set abstraction for the whole batch in one call."""
    n_pad = -(-n // 128) * 128
    call = make_fps_call(PROBLEMS // 8, n_pad, -(-m // 128) * 128, n, m,
                         False)
    text = _compiled_text(call, _shape(one_chip, (3, PROBLEMS, n_pad),
                                       jnp.float32))
    _assert_named_kernel(text, "fps_sample")


@pytest.mark.parametrize("n,m,r", MPINETS_SA, ids=["sa1", "sa2"])
def test_ball_query_compiles(one_chip, n, m, r):
    """128 neighbours of every centre of the batch in one call."""
    n_pad = -(-n // 128) * 128
    call = make_ball_query_call(PROBLEMS, m, n_pad, MAX_ROWS, n, 128, r,
                                False)
    text = _compiled_text(call, _shape(one_chip, (PROBLEMS, m, 3),
                                       jnp.float32),
                          _shape(one_chip, (PROBLEMS, 3, n_pad),
                                 jnp.float32))
    _assert_named_kernel(text, "ball_query")

