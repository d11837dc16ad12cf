"""A cohort wider than one lane row (``ukb1``: 454,787 samples, a plane row
of 14,213 words resident as 112 lane rows).

Every cell before it read genotype rows of at most 128 words, where all
that is linear in the cohort is too small to see. Here: the mask as one
scatter of bits, the selection resolved through the map a shard keeps
and kept by the shard, the programs against numpy at the published
width (a program that drops lane rows past the first, or a mask that
loses bits past word 127, fails), and a filtered record request through
``app.handle`` at two lane rows held to the benchmark's own plain
reference. CPU, the chip's index family forced; the programs' compile
at ``ukb1``'s shapes is tests/test_chip_compile.py's.
"""

import dataclasses
import gc
import hashlib
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.config import BeaconConfig
from sbeacon_tpu.engine import (
    SampleSelection,
    VariantEngine,
    host_match_rows,
    materialize_response,
    materialize_response_loop,
)
from sbeacon_tpu.metadata.memo import KeptSamples
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.plane_kernel import (
    ROW_BLOCK,
    PlaneDeviceIndex,
    gathered_bytes,
    padded_words,
    plane_row_stats,
    resident_shape,
    sample_mask_words,
)
from sbeacon_tpu.ops.scatter_kernel import (
    ScatterDeviceIndex,
    run_selected_scattered,
)
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import flight_recorder
from sbeacon_tpu.testing import synthetic_shard
from sbeacon_tpu.utils.trace import tracer

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
UKB_SAMPLES = 454_787  # benchmark/configs/ukb1.json
UKB_WORDS = 14_213
TERMS = tuple(f"MONDO:{5000 + t:07d}" for t in range(25))


def _loop_mask(selected_idx, n_words: int) -> np.ndarray:
    """``sample_mask_words`` as it was: one scalar write a sample."""
    mask = np.zeros(n_words, dtype=np.uint32)
    for si in selected_idx:
        mask[si // 32] |= np.uint32(1 << (si % 32))
    return mask


@pytest.mark.parametrize(
    "selected",
    [
        [],
        [0],
        [31],
        [UKB_SAMPLES - 1],
        [5, 5, 37, 5, 37],
        list(range(3, UKB_SAMPLES, 25)),
        np.arange(UKB_SAMPLES - 40, UKB_SAMPLES),
    ],
    ids=["empty", "one", "bit31", "last_of_454787", "duplicates",
         "a_term_of_25", "array_at_the_tail"],
)
def test_the_mask_is_one_scatter_of_bits_and_the_same_words(selected):
    got = sample_mask_words(selected, UKB_WORDS)
    assert got.dtype == np.uint32 and got.shape == (UKB_WORDS,)
    np.testing.assert_array_equal(got, _loop_mask(selected, UKB_WORDS))
    assert int(np.bitwise_count(got).sum()) == len(set(np.asarray(selected).tolist()))


def _wide_shard(n_samples, n_rows=600, seed=5, dataset_id="wide", density=0.25):
    """INFO-sourced rows (as every configuration's) with a ``gt`` plane of
    ``n_samples`` carriers a row, made as ``benchmark/corpus.py`` makes it."""
    shard = synthetic_shard(
        n_rows, n_samples=n_samples, seed=seed, dataset_id=dataset_id,
        chroms=["22"], p_multiallelic=0.2, with_gt_planes=False,
    )
    rng = np.random.default_rng(seed + 1)
    words = -(-n_samples // 32)
    g = rng.integers(0, 2**32, (shard.n_rows, words), dtype=np.uint32)
    if density <= 0.25:
        g &= rng.integers(0, 2**32, g.shape, dtype=np.uint32)
    if n_samples % 32:
        g[:, -1] &= np.uint32((1 << (n_samples % 32)) - 1)
    return dataclasses.replace(shard, gt_bits=g)


def _payload(names_by_dataset, **kw) -> VariantQueryPayload:
    return VariantQueryPayload(
        dataset_ids=sorted(names_by_dataset), reference_name="22",
        start_min=1, start_max=1 << 30, end_min=1, end_max=1 << 30,
        alternate_bases="N", include_datasets="HIT",
        requested_granularity="record", include_samples=True,
        selected_samples_only=True, sample_names=names_by_dataset, **kw,
    )


def test_the_selection_reads_the_map_the_shard_keeps_and_sees_a_publish():
    """``_selected_idx`` through ``shard.sample_positions()`` is the
    dictionary it replaced; ``add_index`` builds the map on the publishing
    thread; a dataset submitted again under other sample names is another
    shard, with its own map."""
    shard = _wide_shard(4133, n_rows=200)
    universe = shard.meta["sample_names"]
    wanted = tuple(universe[i] for i in range(7, 4133, 25)) + ("nobody", universe[7])
    payload = _payload({"wide": wanted})
    name_to_idx = {s: k for k, s in enumerate(universe)}
    want = [name_to_idx[s] for s in wanted if s in name_to_idx]
    assert VariantEngine._selected_idx(shard, payload, "wide") == want
    assert VariantEngine._selected_idx(shard, payload, "other") == []

    eng = VariantEngine(BeaconConfig())
    try:
        shard = dataclasses.replace(shard)  # as a publish brings it
        assert "_sample_positions" not in shard.__dict__
        eng.add_index(shard)
        assert shard.__dict__["_sample_positions"] == name_to_idx
        n0 = tracer.stage_counts("engine.select")[0]
        counted = eng.selected_samples
        sel = eng._selection(shard, payload, "wide")
        assert isinstance(sel, SampleSelection)
        assert sel.idx.tolist() == want and len(sel) == len(want)
        np.testing.assert_array_equal(sel.mask, _loop_mask(want, 130))
        assert sel.names.tolist() == [universe[i] for i in want]
        # the same names again, as an equal list: resolved again (what
        # is kept is kept ON the memo's own tuple), one stage sample and
        # the selection's size counted a request
        same = eng._selection(shard, _payload({"wide": list(wanted)}), "wide")
        assert same is not sel and same.idx.tolist() == want
        assert tracer.stage_counts("engine.select")[0] - n0 == 2
        assert eng.selected_samples - counted == 2 * len(want)
        # names as the metadata memo hands them out carry what the engine
        # resolved from them, a shard: the next request reads it there
        kept = _payload({"wide": KeptSamples(wanted)})
        first = eng._selection(shard, kept, "wide")
        assert first.idx.tolist() == want
        assert eng._selection(shard, kept, "wide") is first
        assert eng.selected_samples - counted == 4 * len(want)

        # published again with the cohort in another order
        renamed = dataclasses.replace(
            shard, meta={**shard.meta, "sample_names": universe[::-1]}
        )
        eng.add_index(renamed)
        again = eng._selection(renamed, payload, "wide")
        assert again.idx.tolist() == [4132 - i for i in want]
        # the kept names are resolved anew against the new shard, and the
        # retired shard's entry goes once the shard has
        assert eng._selection(renamed, kept, "wide").idx.tolist() == again.idx.tolist()
        assert set(kept.sample_names["wide"].resolved) == {id(shard), id(renamed)}
        del shard, sel, same, first
        gc.collect()
        eng._selection(dataclasses.replace(renamed), kept, "wide")
        assert id(renamed) in kept.sample_names["wide"].resolved
        assert len(kept.sample_names["wide"].resolved) == 2
        (_key, served, _planes), = eng.index_snapshot()
        assert served is renamed
    finally:
        eng.close()


def test_a_reader_connection_maps_the_store_s_file(tmp_path):
    """A memo miss at biobank width is one statement that probes three
    indexes 18,191 times: every reader thread of a file-backed store
    reads through a mapping of the file, not one ``pread`` a page (eight
    misses at once stood a second each in system calls on the chip's
    hosts, and warm-up's 25 walked the brownout ladder)."""
    from sbeacon_tpu.metadata.store import READ_MMAP_BYTES, MetadataStore

    store, in_memory = MetadataStore(tmp_path / "metadata.sqlite"), MetadataStore()
    try:
        (mapped,), = store.query("PRAGMA mmap_size")
        assert 0 < mapped <= READ_MMAP_BYTES
        # the in-memory store of the tests has no file to map
        assert in_memory.query("PRAGMA mmap_size") in ([], [(0,)])
    finally:
        store.close()
        in_memory.close()


def _popcount_rows(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=1, dtype=np.int64)


def _check_programs(shard, sindex, pindex, specs, masks, record_cap=64):
    """``run_selected_scattered`` (the fused program, a launch a
    query) and ``plane_row_stats`` against numpy on the
    host's own plane."""
    n_words = shard.gt_bits.shape[1]
    res = run_selected_scattered(
        sindex, pindex, specs, masks, window_cap=512, record_cap=record_cap,
    )
    assert res.or_words.shape == (len(specs), n_words)
    assert not res.overflow.any()
    ac, rec_id = shard.cols["ac"], shard.cols["rec_id"]
    hits = 0
    for q, spec in enumerate(specs):
        rows = host_match_rows(shard, spec)
        keep = res.rows[q] >= 0
        np.testing.assert_array_equal(res.rows[q][keep], rows)
        m = masks[q]
        np.testing.assert_array_equal(
            res.pc_call[q][keep], _popcount_rows(shard.gt_bits[rows] & m)
        )
        # a pad lane reads nothing and counts nothing
        assert not res.pc_call[q][~keep].any() and not res.pc_tok[q].any()
        want_or = np.zeros(n_words, np.uint32)
        rc = ac[rows].astype(np.int64)
        if len(rows) and rc.sum() > 0:
            first = int(np.argmax(np.cumsum(rc) > 0))
            sel = rows[rec_id[rows] >= rec_id[rows][first]]
            want_or = np.bitwise_or.reduce(shard.gt_bits[sel] & m, axis=0)
            hits += 1
        np.testing.assert_array_equal(res.or_words[q], want_or)
        or_sel = np.zeros(len(rows), np.int32)
        or_sel[::2] = 1
        counts, ow = plane_row_stats(pindex, rows, m, or_sel=or_sel)
        np.testing.assert_array_equal(
            counts[:, 0], _popcount_rows(shard.gt_bits[rows] & m)
        )
        assert not counts[:, 1:].any()
        np.testing.assert_array_equal(
            ow,
            np.bitwise_or.reduce(
                shard.gt_bits[rows[::2]] & m, axis=0, initial=np.uint32(0)
            ),
        )
    return res, hits


def _specs(shard, rng, n, span):
    pos = shard.cols["pos"]
    out = []
    for _ in range(n):
        i = int(rng.integers(0, len(pos)))
        j = min(len(pos) - 1, i + int(rng.integers(0, span)))
        out.append(QuerySpec(
            "22", int(pos[i]), int(pos[j]), 1, 1 << 30,
            alternate_bases=["N", "N", "T"][int(rng.integers(0, 3))],
        ))
    return out


@pytest.mark.parametrize(
    "n_samples,k,lane_rows",
    [(1000, 4, 1), (2504, 1, 1), (4133, 1, 2)],
    ids=["1000_four_rows_a_lane_row", "2504_one_lane_row", "4133_two_lane_rows"],
)
def test_the_programs_answer_as_numpy_at_every_layout(n_samples, k, lane_rows):
    """The widths the cells before ``ukb1`` run (``mdsp``, ``kg1``) and
    the first past one lane row: 66 queries are 66 launches of the
    one-slot program; every output is what numpy reads from the host's
    plane."""
    shard = _wide_shard(n_samples, seed=11 + n_samples)
    n_words = shard.gt_bits.shape[1]
    assert resident_shape(shard.n_rows, n_words) == (
        -(-shard.n_rows // 128) * 128 // k, 128 * lane_rows)
    sindex, pindex = ScatterDeviceIndex(shard), PlaneDeviceIndex(shard)
    rng = np.random.default_rng(n_samples)
    specs = _specs(shard, rng, 66, 40)
    masks = np.stack([
        np.full(n_words, 0xFFFFFFFF, np.uint32) if q % 5 == 0
        else sample_mask_words(
            rng.choice(n_samples, size=n_samples // 25, replace=False), n_words)
        for q in range(len(specs))
    ])
    before = flight_recorder.launches_by_family().get("plane", 0)
    _res, hits = _check_programs(shard, sindex, pindex, specs, masks)
    assert hits > 30
    assert flight_recorder.launches_by_family()["plane"] - before >= len(specs)
    one, hit = _check_programs(shard, sindex, pindex, specs[:1], masks[:1])
    assert one.rows.shape[0] == 1


#: the outputs of ``run_selected_scattered`` and ``plane_row_stats`` of the
#: tree at 0ccf580 (one gather of 64 slots x R rows) on the seeded inputs of
#: ``test_the_programs_answer_as_the_tree_before_them``: sha256 over dtype,
#: shape and bytes, first sixteen digits, made by running that tree
PARENT_OUTPUTS = {
    (1000, False): {
        "agg": "3949fbfa5241108f", "or": "a7478a70977d4190", "pc": "4451da61f4234654",
        "pt": "3a317e2c8b698b61", "rows": "3d331ae41eb5d50c",
        "stats_c": "ea5b7e382b402387", "stats_or": "068115c36ae4cae6"},
    (1000, True): {
        "agg": "6b5fc66545a025b9", "or": "bb203a7e3efbbbd3", "pc": "106221fe7ec21b4b",
        "pt": "88cff2735bbc8316", "rows": "b74aff97e00c17c0",
        "stats_c": "ef3e7cf4bce464c7", "stats_or": "79b386f050b1c5df"},
    (2504, False): {
        "agg": "235d64d9c789f99b", "or": "927a78d263cb1152", "pc": "49f991bc1dd6f530",
        "pt": "3a317e2c8b698b61", "rows": "6171b04151421746",
        "stats_c": "4e5d3ed587b12647", "stats_or": "d475f754beca3ac6"},
    (2504, True): {
        "agg": "f69d34b476cc6c64", "or": "2f8e92e29a228a86", "pc": "dec2810d54a72d8a",
        "pt": "c6d5fa6143763f31", "rows": "0a6c440d71e40e67",
        "stats_c": "e3f6a7ff0eaaae1d", "stats_or": "43027740f184134b"},
    (4133, False): {
        "agg": "330f2e957c7cb554", "or": "277f2c165be96677", "pc": "3b79137f985c9ba8",
        "pt": "3a317e2c8b698b61", "rows": "0fb3617a877fc3fa",
        "stats_c": "3c5f98a25a3c82ec", "stats_or": "9d3f6f16863fddbb"},
    (4133, True): {
        "agg": "733c38f90790074c", "or": "221cfbbe9b75d3a0", "pc": "626e0a6c220b429d",
        "pt": "b8fd4310d6e350cf", "rows": "dc08e6deef97f3bf",
        "stats_c": "c0c170b6398187a6", "stats_or": "f5897f589ee42768"},
}


def _digest(arr) -> str:
    arr = np.ascontiguousarray(arr)
    return hashlib.sha256(
        str(arr.dtype).encode() + str(arr.shape).encode() + arr.tobytes()
    ).hexdigest()[:16]


@pytest.mark.parametrize("n_samples,with_counts", sorted(PARENT_OUTPUTS))
def test_the_programs_answer_as_the_tree_before_them(n_samples, with_counts):
    """``_selected_batch`` and ``_plane_stats`` at 1000 samples (k 4),
    2504 (one lane row) and 4133 (two), over shards with and without
    genotype-derived counts (the four count planes): every output, bit
    for bit, is what the tree at 0ccf580 returned for the same seeded
    inputs (a pad lane's counts apart: it read row 0 there and reads
    nothing now, so both sides are held at 0 there)."""
    from sbeacon_tpu.index.columnar import FLAG

    shard = synthetic_shard(
        1501, n_samples=n_samples, seed=n_samples, dataset_id="d", chroms=["7"],
        p_multiallelic=0.3, with_gt_planes=True, plane_density=0.25,
    )
    if with_counts:
        shard.cols["flags"][::3] &= ~np.int32(FLAG.AC_INFO | FLAG.AN_INFO)
    sindex, pindex = ScatterDeviceIndex(shard), PlaneDeviceIndex(shard)
    rng = np.random.default_rng(n_samples + with_counts)
    pos = shard.cols["pos"]
    n_words = shard.gt_bits.shape[1]
    specs, masks = [], []
    for _ in range(70):
        i = int(rng.integers(0, len(pos)))
        j = min(len(pos) - 1, i + int(rng.integers(0, 60)))
        specs.append(QuerySpec(
            "7", int(pos[i]), int(pos[j]), 1, 1 << 30,
            alternate_bases=["N", "N", "T"][int(rng.integers(0, 3))],
        ))
        masks.append(sample_mask_words(
            rng.choice(n_samples, n_samples // 25, replace=False), n_words))
    masks = np.stack(masks)
    res = run_selected_scattered(
        sindex, pindex, specs, masks, window_cap=2048, record_cap=1024)
    keep = res.rows >= 0
    rows = np.sort(rng.choice(shard.n_rows, 300, replace=False))
    or_sel = (rng.random(300) < 0.5).astype(np.int32)
    counts, stats_or = plane_row_stats(pindex, rows, masks[0], or_sel=or_sel)
    got = {
        "rows": res.rows, "or": res.or_words,
        "pc": np.where(keep, res.pc_call, 0), "pt": np.where(keep, res.pc_tok, 0),
        "agg": np.stack([
            res.exists, res.call_count, res.n_variants, res.all_alleles_count,
            res.n_matched, res.overflow,
        ]).astype(np.int64),
        "stats_c": counts, "stats_or": stats_or,
    }
    assert {k: _digest(v) for k, v in got.items()} == PARENT_OUTPUTS[
        (n_samples, with_counts)]


@pytest.fixture(scope="module")
def published_width():
    """A few hundred rows of a 454,787-sample cohort (17 MB of plane) on
    the device as ``ukb1``'s lie: ``[n, 14336]``, 112 lane rows a row."""
    shard = _wide_shard(UKB_SAMPLES, n_rows=300, seed=43)
    assert shard.gt_bits.shape == (shard.n_rows, UKB_WORDS)
    assert padded_words(UKB_WORDS) == 14_336 == 112 * 128
    pindex = PlaneDeviceIndex(shard)
    assert shard.n_rows == 300  # resident in whole steps of 128 rows
    assert pindex.gt.shape == (384, 14_336)
    assert pindex.nbytes_hbm() == 384 * 57_344
    return shard, ScatterDeviceIndex(shard), pindex


def test_the_programs_at_the_published_width(published_width):
    """454,787 samples, a mask of 18,191 or 18,192 bits spread over all
    14,213 words: the popcounts and the carrier words of every lane row,
    and the bytes the launches read (blocks of eight matched rows of
    57,344 B, nothing for a miss)."""
    shard, sindex, pindex = published_width
    rng = np.random.default_rng(1)
    specs = _specs(shard, rng, 9, 30)
    terms = [int(t) for t in rng.integers(0, 25, len(specs))]
    masks = np.stack([
        sample_mask_words(np.arange(t, UKB_SAMPLES, 25), UKB_WORDS) for t in terms
    ])
    assert {int(np.bitwise_count(m).sum()) for m in masks} <= {18_191, 18_192}
    assert masks[:, 128:].any() and masks[:, -1].any()
    gathered = flight_recorder.plane_gather_bytes
    launches = flight_recorder.launches_by_family().get("plane", 0)
    res = run_selected_scattered(
        sindex, pindex, specs, masks, window_cap=512, record_cap=64)
    n_matched = [len(host_match_rows(shard, s)) for s in specs]
    assert max(n_matched) > ROW_BLOCK  # a query of two blocks among them
    want = sum(-(-n // ROW_BLOCK) for n in n_matched) * ROW_BLOCK * 57_344
    assert flight_recorder.plane_gather_bytes - gathered == want
    assert want == gathered_bytes(pindex.gt, n_matched, 1)
    # a launch a query
    assert flight_recorder.launches_by_family()["plane"] - launches == len(specs)
    ring = flight_recorder.snapshot()["ring"]["entries"]
    assert "gatherBytes" in [e for e in ring if e["family"] == "plane"][-1]
    _res, hits = _check_programs(shard, sindex, pindex, specs, masks)
    assert hits >= 5
    # words past the first lane row carry carriers, and the last word's
    # spare bits none
    assert res.or_words[:, 128:].any()
    assert not (res.or_words[:, -1] >> np.uint32(UKB_SAMPLES % 32)).any()


def test_a_response_at_the_published_width_is_the_loops(published_width):
    """``materialize_response`` over a resolved selection of 18,192
    samples (carriers and names picked in numpy) equals the per-record
    loop's, through the fused outputs, the device planes and the host's."""
    shard, sindex, pindex = published_width
    universe = shard.meta["sample_names"]
    positions = list(range(2, UKB_SAMPLES, 25))
    payload = _payload({"wide": tuple(universe[i] for i in positions)})
    sel = SampleSelection(shard, positions)
    assert len(sel) == 18_192 and sel.names[-1] == universe[positions[-1]]
    pos = shard.cols["pos"]
    spec = QuerySpec("22", int(pos[100]), int(pos[112]), 1, 1 << 30,
                     alternate_bases="N")
    rows = host_match_rows(shard, spec, ref_wildcard=True)
    assert len(rows) >= 5
    want = materialize_response_loop(
        shard, rows, payload, chrom_label="22", dataset_id="wide",
        selected_idx=positions,
    )
    assert want.exists and 4_000 < len(want.sample_indices) <= 18_192
    res = run_selected_scattered(
        sindex, pindex, [spec], sel.mask[None, :], window_cap=512, record_cap=64)
    keep = res.rows[0] >= 0
    fused = (res.pc_call[0][keep], res.pc_tok[0][keep], res.or_words[0])
    for kw in ({"fused": fused}, {"plane_index": pindex}, {}):
        got = materialize_response(
            shard, rows, payload, chrom_label="22", dataset_id="wide",
            selected_idx=sel, **kw,
        )
        assert got == want, sorted(kw)
        assert type(got.sample_indices[0]) is int
    # a plain list of positions resolves to the same selection
    assert materialize_response(
        shard, rows, payload, chrom_label="22", dataset_id="wide",
        selected_idx=positions,
    ) == want
    # without a selection: every carrier of the cohort, by name
    whole = dataclasses.replace(payload, selected_samples_only=False, sample_names={})
    assert materialize_response(
        shard, rows, whole, chrom_label="22", dataset_id="wide", plane_index=pindex,
    ) == materialize_response_loop(
        shard, rows, whole, chrom_label="22", dataset_id="wide")


# -- a filtered record request through app.handle, two lane rows wide --------

N_WIDE = 4133  # 130 plane words: two lane rows


def _submission(ds: str, samples: list) -> dict:
    """As ``benchmark/corpus.metadata_submission``: individual i carries
    term i mod 25."""
    idx = range(len(samples))
    return {
        "datasetId": ds, "assemblyId": "GRCh38", "vcfLocations": [],
        "dataset": {"name": ds, "description": "two lane rows"}, "index": True,
        "individuals": [
            {"id": f"{ds}-I{i}", "sex": {"id": "NCIT:C16576", "label": "-"},
             "diseases": [{"diseaseCode": {"id": TERMS[i % len(TERMS)]}}]}
            for i in idx
        ],
        "biosamples": [
            {"id": f"{ds}-B{i}", "individualId": f"{ds}-I{i}"} for i in idx
        ],
        "runs": [
            {"id": f"{ds}-R{i}", "biosampleId": f"{ds}-B{i}",
             "individualId": f"{ds}-I{i}"} for i in idx
        ],
        "analyses": [
            {"id": f"{ds}-A{i}", "runId": f"{ds}-R{i}",
             "biosampleId": f"{ds}-B{i}", "individualId": f"{ds}-I{i}",
             "vcfSampleId": samples[i]} for i in idx
        ],
    }


@pytest.fixture(scope="module")
def reference():
    """``benchmark/reference.py``: the plain reference, independent of
    the program."""
    sys.path.insert(0, str(BENCH))
    try:
        import reference as module
    finally:
        sys.path.remove(str(BENCH))
    return module


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """One 4,133-sample dataset with its plane on the device and every
    sample's metadata behind ``app.handle``; ``engine.search`` tapped."""
    patch = pytest.MonkeyPatch()
    patch.setattr(
        engine_mod, "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    first = jax.local_devices()[:1]
    patch.setattr(jax, "local_devices", lambda *a, **kw: first)
    app = BeaconApp(BeaconConfig.from_env(tmp_path_factory.mktemp("wide_root")))
    shard = _wide_shard(N_WIDE, n_rows=1500, seed=77, dataset_id="wide-0")
    app.engine.add_index(shard)
    st, doc = app.handle(
        "POST", "/submit", body=_submission("wide-0", shard.meta["sample_names"]))
    assert st == 200, doc
    calls = []
    search = app.engine.search

    def tapped(payload):
        responses = search(payload)
        calls.append((payload, responses))
        return responses

    app.engine.search = tapped
    try:
        yield app, shard, calls
    finally:
        app.close()
        app.engine.close()
        patch.undo()


@pytest.mark.parametrize(
    "term,width,descendants",
    [(3, 0, True), (11, 4_000, True), (24, 9_000, False), (0, 2_500, None)],
)
def test_a_filtered_record_request_two_lane_rows_wide(
    node, reference, term, width, descendants
):
    """Filters, selection, mask, one launch, carriers, envelope: the
    served envelope, the samples the filter resolved to and the engine's
    answer are the plain reference's, with no difference allowed; the new
    stage and counters moved as the request says."""
    app, shard, calls = node
    c = shard.cols
    snv = np.flatnonzero((c["ref_len"] == 1) & (c["alt_len"] == 1) & (c["ac"] > 0))
    pos = int(c["pos"][snv[len(snv) // 3 + term]])
    flt = {"id": TERMS[term], "scope": "individuals"}
    if descendants is not None:
        flt["includeDescendantTerms"] = descendants
    body = {"query": {
        "requestedGranularity": "record",
        "includeResultsetResponses": "HIT",
        "requestParameters": {
            "assemblyId": "GRCh38", "referenceName": "22",
            "start": [max(0, pos - 1 - width)], "end": [pos + width],
            "alternateBases": "N",
        },
        "filters": [flt],
        "pagination": {"skip": 0, "limit": 100},
    }}
    del calls[:]
    selects = tracer.stage_counts("engine.select")[0]
    selected = app.engine.selected_samples
    gathered = flight_recorder.plane_gather_bytes
    launches = flight_recorder.launches_by_family().get("plane", 0)
    fallbacks = sum(flight_recorder.fallbacks_by_site().values())
    st, doc = app.handle("POST", "/g_variants", body=body)
    assert st == 200, doc
    (payload, responses), = calls
    want_sel = [i for i in range(N_WIDE) if i % 25 == term]
    assert tracer.stage_counts("engine.select")[0] - selects == 1
    assert app.engine.selected_samples - selected == len(want_sel)
    assert flight_recorder.launches_by_family()["plane"] - launches == 1
    assert sum(flight_recorder.fallbacks_by_site().values()) == fallbacks
    # one slot, so one mask up and one row of carrier words back
    last = [e for e in flight_recorder.snapshot()["ring"]["entries"]
            if e["family"] == "plane"][-1]
    assert (last["tier"], last["specs"], last["padded"]) == (1, 1, 1)
    n_matched = len(host_match_rows(shard, QuerySpec(
        "22", payload.start_min, payload.start_max, payload.end_min,
        payload.end_max, alternate_bases="N"), ref_wildcard=True))
    assert n_matched >= 1
    assert flight_recorder.plane_gather_bytes - gathered == (
        -(-n_matched // ROW_BLOCK) * ROW_BLOCK * 256 * 4)
    assert last["gatherBytes"] == flight_recorder.plane_gather_bytes - gathered

    ref_shard = reference.RefShard.of(shard)
    q = reference.parse_body(body)
    names = payload.sample_names["wide-0"]
    # the memo's own tuple, with what the engine resolved from it
    assert isinstance(names, KeptSamples)
    (served, kept_sel), = names.resolved.values()
    assert served() is shard and len(kept_sel) == len(want_sel)
    at = [ref_shard.sample_names.index(n) for n in names]
    assert sorted(at) == want_sel
    want = reference.answers([ref_shard], q, lambda s, _q: at)
    assert reference.envelope_mismatch(
        json.loads(json.dumps(doc)), reference.envelope_facts(q, want)) is None
    assert reference.answers_mismatch(responses, want) is None
    assert want[0].exists and len(want[0].sample_indices) > 20

    _st, metrics = app.handle("GET", "/metrics")
    assert metrics["engine"]["selected_samples"] == app.engine.selected_samples
    assert metrics["device"]["plane_gather_bytes"] == (
        flight_recorder.plane_gather_bytes)
    _st, status = app.handle("GET", "/debug/status")
    assert status["stages"]["engine.select"]["count"] >= 1
    assert status["device"]["launches"]["planeGatherBytes"] == (
        flight_recorder.plane_gather_bytes)
