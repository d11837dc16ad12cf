"""The plane programs compiled for the chip at the benchmark's real
shapes, without the chip: the TPU's compiler is installed here and
compiles for a described v5e. Nothing runs, so these say nothing of
results or times; they hold the per-launch programs to what they may
touch. A plane resident ``[n, 79]`` was re-tiled whole inside every
launch (5.12 GB of temp at 1e7 rows, refused outright at 2e7); resident
``[n, 128]`` the gather reads it as it lies, and so it does the
``[ceil(n / 4), 128]`` of a 1000-sample cohort, four rows to a lane row.
The fused program read its windows word by word, 1.7 ms a column a
launch over ``mds``' 6.4e7 rows; it reads them as the lane rows they lie
in, every column a bitcast of the 1-D array that is resident.

All of them live in this one file: the worker that is given it loads
the TPU's library, and keeps it.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from sbeacon_tpu.ops.kernel import (
    _PAD_FILLS,
    PACK_WIDTH,
    QuerySpec,
    _query_batch,
    bisect_iters,
    encode_queries,
    pack_queries,
    pad_columns,
)
from sbeacon_tpu.ops.plane_kernel import (
    _plane_stats,
    _write_rows,
    padded_words,
    resident_shape,
)
from sbeacon_tpu.ops.query_pack import N_QWORDS
from sbeacon_tpu.ops.scatter_kernel import (
    SELECTED_SLOTS,
    ScatterDeviceIndex,
    _selected_batch,
)

KG1_ROWS = 10_000_000  # benchmark/configs/kg1.json
KG1_WORDS = 79  # 2504 samples
MDSP_ROWS = 2_999_000  # benchmark/configs/mdsp.json, one of sixteen datasets
MDSP_WORDS = 32  # 1000 samples: four rows to a lane row
TILE = 128


@pytest.fixture(scope="module")
def chips():
    """One ``SingleDeviceSharding`` per chip of a described v5e 2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield [SingleDeviceSharding(d) for d in topo.devices]
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chips):
    return chips[0]


def _shape(one_chip, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)


def _plane_sized_outputs(compiled, n_rows: int) -> list[str]:
    """Instructions of the optimised program whose output has the
    plane's row count, parameters and views of one apart (the loop of
    ``reduce_rows`` is handed the plane as an element of its state)."""
    made = re.compile(rf"= \w+\[{n_rows},\d+\]\S* (\S+?)\(")
    views = {"parameter", "get-tuple-element", "bitcast"}
    return [
        line.strip()[:160]
        for line in compiled.as_text().splitlines()
        for m in [made.search(line)]
        if m and m.group(1) not in views
    ]


def _selected(
    one_chip, n_rows, cap, C, with_counts=False, n_words=KG1_WORDS,
    group=1, exact=True,
):
    """The fused program of a launch group of ``group`` datasets of
    ``n_rows`` rows each: a slot over each dataset's own buffers."""
    n_tiles = n_rows // TILE + 1 + ScatterDeviceIndex.MAX_C
    plane = _shape(one_chip, *resident_shape(n_rows, n_words))
    return _selected_batch.lower(
        (_shape(one_chip, n_tiles, 8, TILE),) * group,
        ((plane,) * (4 if with_counts else 1),) * group,
        _shape(one_chip, group, 1 + N_QWORDS + n_words),
        T=TILE, CAP=cap, C=C, exact_only=exact,
        R=min(1024, cap), seg_k=2,
    ).compile()


def _stats(one_chip, n_rows, n_words, R, with_counts=False):
    plane = _shape(one_chip, *resident_shape(n_rows, n_words))
    return _plane_stats.lower(
        plane, plane, plane, plane,
        _shape(one_chip, R), _shape(one_chip), _shape(one_chip, R),
        _shape(one_chip, n_words),
        R=R, with_counts=with_counts, with_or=True,
    ).compile()


def _rows_that_fit(with_counts: bool) -> int:
    """Four planes of 1e7 rows do not fit one chip: a quarter each."""
    return KG1_ROWS // 4 if with_counts else KG1_ROWS


def _holds_no_plane_copy(compiled, n_rows, n_words=KG1_WORDS):
    lane_rows, lanes = resident_shape(n_rows, n_words)
    plane_bytes = lane_rows * lanes * 4
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes // 8
    assert _plane_sized_outputs(compiled, lane_rows) == []


@pytest.mark.parametrize(
    "cap,C,with_counts",
    [(128, 1, False), (128, None, True), (2048, None, False)],
)
def test_selected_batch_touches_no_whole_plane(one_chip, cap, C, with_counts):
    n_rows = _rows_that_fit(with_counts)
    _holds_no_plane_copy(
        _selected(one_chip, n_rows, cap, C, with_counts), n_rows
    )


@pytest.mark.parametrize("R,with_counts", [(128, False), (8192, True)])
def test_plane_stats_touches_no_whole_plane(one_chip, R, with_counts):
    n_rows = _rows_that_fit(with_counts)
    _holds_no_plane_copy(
        _stats(one_chip, n_rows, KG1_WORDS, R, with_counts), n_rows
    )


def test_selected_batch_compiles_at_twice_the_rows(one_chip):
    """2e7 rows, a quarter of the callset: refused while the launch
    copied the plane (9.54 GB of temp beside 6.56 GB of arguments)."""
    _holds_no_plane_copy(
        _selected(one_chip, 2 * KG1_ROWS, 128, 1), 2 * KG1_ROWS
    )


def test_selected_batch_compiles_on_an_owner_that_is_not_chip_0(chips):
    """``kg4``: each of four chips owns 2e7 rows with their plane, and
    the program is compiled for the chip its operands are committed to."""
    owner = chips[3]
    assert owner.device_set != chips[0].device_set
    compiled = _selected(owner, 2 * KG1_ROWS, 128, 1)
    _holds_no_plane_copy(compiled, 2 * KG1_ROWS)
    # the first operand: the tuple of the group's tile arrays
    assert compiled.input_shardings[0][0][0].device_set == owner.device_set


def test_upload_writes_its_chunk_in_place(one_chip):
    """The resident plane is donated to each chunk's write and comes
    back as its output: beside it the program holds the chunk alone."""
    rows = 256 * 1024 * 1024 // (KG1_WORDS * 4) // 8 * 8
    wp = padded_words(KG1_WORDS)
    compiled = _write_rows.lower(
        _shape(one_chip, KG1_ROWS, wp),
        _shape(one_chip, rows, KG1_WORDS),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == KG1_ROWS * wp * 4
    assert memory.temp_size_in_bytes <= 2 * rows * wp * 4


#: what a launch may hold beside its arguments and outputs, whatever the
#: planes' width, the slots and R: the match's windows and scans and ONE
#: block of eight gathered rows (``reduce_rows``). One gather of all 64
#: slots x R rows held 4.2 / 33.6 MB at ``mdsp`` and 0.47 / 3.76 GB at
#: ``ukb1``
WORKSPACE_BYTES = 4 << 20


@pytest.mark.parametrize(
    "cap,C", [(128, 1), (128, None), (2048, None)],
)
def test_packed_selected_batch_at_mdsp_shapes(one_chip, cap, C):
    """``mdsp``: 2,999,000 rows of 32 words, four to a lane row. The
    program's temp is a block of the rows it gathers, never the rows of
    a whole launch (33.6 MB at R = 1024) nor the plane (384 MB)."""
    # whole steps of 128 rows: 2,999,040 of them, four to a lane row
    assert resident_shape(MDSP_ROWS, MDSP_WORDS) == (749_760, 128)
    compiled = _selected(one_chip, MDSP_ROWS, cap, C, n_words=MDSP_WORDS)
    _holds_no_plane_copy(compiled, MDSP_ROWS, MDSP_WORDS)
    assert compiled.memory_analysis().temp_size_in_bytes <= WORKSPACE_BYTES


@pytest.mark.parametrize(
    "cap,C,exact,with_counts",
    [(128, 1, True, False), (2048, None, False, False), (512, None, True, True)],
)
def test_group_launch_at_mdsp_shapes(one_chip, cap, C, exact, with_counts):
    """``mdsp.samples``' launch since PR 44: ALL sixteen datasets of the
    chip in one program, a slot over each dataset's own tiles and
    planes (2,999,000 rows of 32 words each). Its arguments are the
    sixteen resident sets as they lie (nothing stacked: no temp the
    size of a plane, no output with a plane's rows), its workspace is
    still a block of gathered rows beside the match's own, now of
    sixteen slots, and what comes back is ONE ``int32[16, 8 + 3R + W]``.
    With their count planes four such datasets fill the chip."""
    group = 4 if with_counts else SELECTED_SLOTS
    compiled = _selected(
        one_chip, MDSP_ROWS, cap, C, with_counts, n_words=MDSP_WORDS,
        group=group, exact=exact,
    )
    _holds_no_plane_copy(compiled, MDSP_ROWS, MDSP_WORDS)
    memory = compiled.memory_analysis()
    lane_rows, lanes = resident_shape(MDSP_ROWS, MDSP_WORDS)
    planes = 4 if with_counts else 1
    assert memory.argument_size_in_bytes >= group * (
        planes * lane_rows * lanes * 4 + MDSP_ROWS * 32
    )
    assert memory.temp_size_in_bytes <= WORKSPACE_BYTES
    R = min(1024, cap)
    (out,) = compiled.out_info if isinstance(
        compiled.out_info, (list, tuple)
    ) else (compiled.out_info,)
    assert out.shape == (group, 8 + 3 * R + MDSP_WORDS)


def test_packed_plane_stats_and_upload_at_mdsp_shapes(one_chip):
    plane = _shape(one_chip, *resident_shape(MDSP_ROWS, MDSP_WORDS))
    compiled = _stats(one_chip, MDSP_ROWS, MDSP_WORDS, 1024)
    _holds_no_plane_copy(compiled, MDSP_ROWS, MDSP_WORDS)
    # a 256 MiB chunk of 4m host rows crosses as the [m, 128] it fills
    lane_rows = 256 * 1024 * 1024 // 512
    memory = _write_rows.lower(
        plane, _shape(one_chip, lane_rows, 128),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile().memory_analysis()
    assert memory.alias_size_in_bytes == 749_760 * 512
    assert memory.temp_size_in_bytes <= 2 * lane_rows * 512


UKB1_ROWS = 160_000  # benchmark/configs/ukb1.json
UKB1_WORDS = 14_213  # 454,787 samples: a row is 112 lane rows, 57,344 B


@pytest.mark.parametrize(
    "cap,C", [(128, 1), (128, None), (512, None), (2048, None)],
)
def test_selected_batch_at_ukb1_shapes(one_chip, cap, C):
    """``ukb1``: a biobank-width plane, ``int32[160000, 14336]``, 9.175
    GB resident, every window-cap tier (R = 128, 128, 512, 1024) of the
    program at ONE slot, the group of one the engine launches there. The workspace is a block of eight rows (8 x 57,344 B)
    beside the match's own, so ``warm_app`` can execute every tier
    beside the resident plane: gathered whole, R = 1024 x 64 slots was
    3.76 GB of temp before the AND and the OR. The gather the compiler
    kept reads whole rows, eight at a step."""
    assert resident_shape(UKB1_ROWS, UKB1_WORDS) == (UKB1_ROWS, 14_336)
    compiled = _selected(one_chip, UKB1_ROWS, cap, C, n_words=UKB1_WORDS)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= UKB1_ROWS * 14_336 * 4
    assert memory.temp_size_in_bytes <= WORKSPACE_BYTES
    # the slot's outputs: its rows and counts, its carrier words
    assert memory.output_size_in_bytes <= 3 * 1024 * 4 + 14_336 * 4 + 4096
    assert _plane_sized_outputs(compiled, UKB1_ROWS) == []
    assert re.search(
        r"= s32\[8,14336\]\S* gather\(.*slice_sizes=\{1,14336\}",
        compiled.as_text(),
    )


@pytest.mark.parametrize("R", [128, 1024, 8192])
def test_plane_stats_at_ukb1_shapes(one_chip, R):
    """``plane_row_stats``' program reads through the same blocks:
    8,192 rows of 57,344 B gathered at once were 470 MB."""
    memory = _stats(one_chip, UKB1_ROWS, UKB1_WORDS, R).memory_analysis()
    assert memory.temp_size_in_bytes <= WORKSPACE_BYTES


MDS_ROWS_PADDED = 63_971_328  # benchmark/configs/mds.json: 32 x 1,999,000


def _fused(one_chip, n_padded, n_shards, batch):
    """``_query_batch`` for an index of ``n_padded`` rows: a stack of
    ``n_shards`` datasets, or with none of them a ``DeviceIndex``."""
    struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    # resident shapes and types as ``pad_columns`` makes them
    empty = {
        k: np.zeros((0, 4), np.uint32) if k == "alt_prefix"
        else np.zeros(0, np.int32)
        for k in _PAD_FILLS
    }
    arrays = {
        k: jax.ShapeDtypeStruct(
            (n_padded,) + v.shape[1:], v.dtype, sharding=one_chip
        )
        for k, v in pad_columns(empty, 0, 1024).items()
    }
    arrays["chrom_offsets"] = _shape(
        one_chip, *((n_shards, 27) if n_shards else (27,))
    )
    queries = [QuerySpec("1", 1, 2, 1, 2)] * batch
    enc = encode_queries(queries, [0] * batch if n_shards else None)
    # the batch as run_queries uploads it: ONE packed array
    return _query_batch.lower(
        arrays,
        struct(pack_queries(enc)),
        window_cap=2048, record_cap=1024, n_iters=bisect_iters(n_padded),
    ).compile()


@pytest.mark.parametrize(
    "n_padded,n_shards,batch",
    [(MDS_ROWS_PADDED, 32, 32), (8192, 0, 8)],
    ids=["mds_fused_stack", "device_index_8192_rows"],
)
def test_fused_program_reads_its_windows_in_lane_rows(
    one_chip, n_padded, n_shards, batch
):
    """Every gather of more than a word a query (the bisection's
    ``pos[mid]`` is one) takes whole 128-lane rows. Where the columns
    are larger than a launch's windows, nothing but a parameter, or a
    view of one, has a column's size (the compiler moves the small
    index's 32 kB columns to faster memory whole, as it may)."""
    compiled = _fused(one_chip, n_padded, n_shards, batch)
    text = compiled.as_text()
    gathers = re.findall(
        r"= \w+\[([\d,]+)\]\S* gather\(.*?slice_sizes=\{([\d,]+)\}", text
    )
    windows = [
        sizes.split(",")
        for dims, sizes in gathers
        if np.prod([int(d) for d in dims.split(",")]) > batch
    ]
    assert len(windows) >= 11  # ten int32 columns and alt_prefix
    assert all("128" in sizes for sizes in windows), windows
    if not n_shards:
        return
    column = re.compile(
        rf"= \w+\[({n_padded}|{n_padded // 128},128)(,4)?\]\S* (\S+?)\("
    )
    made = {m.group(3) for m in map(column.search, text.splitlines()) if m}
    assert made <= {"parameter", "bitcast", "get-tuple-element"}, made
    assert compiled.memory_analysis().temp_size_in_bytes < n_padded * 4 // 8


MDS4_DATASETS = 128  # benchmark/configs/mds4.json: 32 a chip on a 2x2 host
MDS4_ROWS = 1_999_000  # a dataset; synthetic_shard adds a few rows to it


def test_mesh_program_compiles_at_mds4_shapes(chips):
    """The engine's mesh program (``parallel/mesh._local_query`` under
    ``shard_map``, one launch a multi-dataset request) for the four
    described chips at ``mds4``'s shapes: the stack ``[128, n]`` with 32
    datasets a chip, one query replicated. A chip's arguments are its
    slice of the eleven columns, the psums are all-reduces the compiler
    keeps, every window is read in lane rows as the fused program reads
    them. Resident ``[D, n]`` the compiler re-tiled all ten row columns
    whole inside every launch (ten copies of ``s32[4,8,15680,128]``,
    256 MB each); resident in lane rows ONE such copy is left, of
    ``pos`` for the bisection's word probes (PERF.md 7). A compile,
    never a time."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sbeacon_tpu.ops.kernel import DeviceIndex, padded_rows
    from sbeacon_tpu.parallel.mesh import AXIS, _build_sharded_fn

    mesh = Mesh(np.array([next(iter(c.device_set)) for c in chips]), (AXIS,))
    assert mesh.devices.size == 4
    n_padded = padded_rows(MDS4_ROWS + 64, DeviceIndex.PAD_UNIT)
    sliced, whole = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    empty = {
        k: np.zeros((0, 4), np.uint32) if k == "alt_prefix"
        else np.zeros(0, np.int32)
        for k in _PAD_FILLS
    }
    # resident as parallel/mesh.lane_rows lays them: [D, n / 128, 128]
    arrays = {
        k: jax.ShapeDtypeStruct(
            (MDS4_DATASETS, n_padded // 128, 128) + v.shape[1:], v.dtype,
            sharding=sliced,
        )
        for k, v in pad_columns(empty, 0, 1024).items()
    }
    arrays["chrom_offsets"] = jax.ShapeDtypeStruct(
        (MDS4_DATASETS, 27), jnp.int32, sharding=sliced
    )
    # the one query as sharded_query puts it: ONE packed array, whole on
    # every chip
    packed = pack_queries(encode_queries([QuerySpec("1", 1, 2, 1, 2)]))
    assert packed.shape == (1, PACK_WIDTH) and packed.dtype == np.int32
    fn = _build_sharded_fn(mesh, AXIS, 2048, 1024, bisect_iters(n_padded))
    compiled = fn.lower(
        arrays,
        jax.ShapeDtypeStruct(packed.shape, packed.dtype, sharding=whole),
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    gathers = re.findall(
        r"= \w+\[([\d,]+)\]\S* gather\(.*?slice_sizes=\{([\d,]+)\}", text
    )
    local = MDS4_DATASETS // 4
    windows = [
        sizes.split(",")
        for dims, sizes in gathers
        if np.prod([int(d) for d in dims.split(",")]) > local
    ]
    assert len(windows) >= 11 and all("128" in w for w in windows), windows
    memory = compiled.memory_analysis()
    columns = sum(
        int(np.prod(a.shape[1:])) * a.dtype.itemsize for a in arrays.values()
    )
    # 32 datasets' columns a chip: 3.9 GB of its 16.9
    assert 0 <= memory.argument_size_in_bytes - local * columns < (1 << 20)
    assert 3.5e9 < local * columns < 4.2e9
    column = local * n_padded * 4
    whole = re.findall(rf"= s32\[4,8,{n_padded // 128},128\]\S* copy\(", text)
    assert len(whole) <= 1, whole
    assert memory.temp_size_in_bytes < column + (8 << 20)
