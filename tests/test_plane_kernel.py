"""Device-resident genotype planes (ops/plane_kernel.py): the device
masked popcounts / OR-reduction must keep materialize_response
bit-identical to the loop spec, across INFO-sourced, genotype-derived,
and ploidy>2-overflow shards (VERDICT r3 #2)."""

import dataclasses
import random

import numpy as np
import pytest

from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records


def _sweep(recs, names, *, seed, n_trials=25):
    from sbeacon_tpu.engine import (
        host_match_rows,
        materialize_response,
        materialize_response_loop,
    )
    from sbeacon_tpu.ops.plane_kernel import PlaneDeviceIndex

    rng = random.Random(seed)
    shard = build_index(recs, dataset_id="pk", sample_names=names)
    pindex = PlaneDeviceIndex(shard)
    pos = shard.cols["pos"]
    cases = 0
    for trial in range(n_trials):
        p = int(pos[rng.randrange(len(pos))])
        spec = QuerySpec(
            "7",
            max(1, p - rng.randint(0, 300)),
            p + rng.randint(0, 300),
            1,
            1 << 30,
            alternate_bases=rng.choice(["N", None, "T"]),
            variant_type=rng.choice([None, "DEL", "CNV"]),
        )
        rows = host_match_rows(shard, spec)
        for gran in ("boolean", "count", "record"):
            for details in (True, False):
                for sel in (None, [0, 3, 8], []):
                    payload = VariantQueryPayload(
                        dataset_ids=["pk"],
                        reference_name="7",
                        start_min=spec.start_min,
                        start_max=spec.start_max,
                        end_min=1,
                        end_max=1 << 30,
                        requested_granularity=gran,
                        include_datasets="HIT" if details else "NONE",
                        include_samples=True,
                        selected_samples_only=sel is not None,
                    )
                    kw = dict(
                        chrom_label="7",
                        dataset_id="pk",
                        selected_idx=sel,
                    )
                    want = materialize_response_loop(
                        shard, rows, payload, **kw
                    )
                    got = materialize_response(
                        shard, rows, payload, plane_index=pindex, **kw
                    )
                    assert got == want, (
                        f"trial={trial} gran={gran} details={details} "
                        f"sel={sel}\n{got}\n{want}"
                    )
                    cases += 1
    assert cases
    return pindex


def test_device_planes_genotype_derived():
    """Genotype-derived counting shard (p_no_acan + ploidy>2 overflow):
    pc/tok popcounts AND the OR run on device."""
    rng = random.Random(41)
    recs = random_records(
        rng,
        chrom="7",
        n=300,
        n_samples=9,
        p_multiallelic=0.35,
        p_symbolic=0.1,
        p_no_acan=0.6,
    )
    for rec in recs[::6]:
        rec.genotypes[rng.randrange(9)] = "1|1|1"
        rec.ac = None
        rec.an = None
    pindex = _sweep(recs, [f"S{i}" for i in range(9)], seed=5)
    assert pindex.has_counts


def test_device_planes_info_sourced():
    """All-INFO shard: only the gt plane is uploaded (count planes are
    never read) and sample extraction still matches the spec."""
    rng = random.Random(43)
    recs = random_records(rng, chrom="7", n=300, n_samples=9, p_no_acan=0.0)
    pindex = _sweep(recs, [f"S{i}" for i in range(9)], seed=6)
    assert not pindex.has_counts
    assert pindex.gt2 is None


def test_engine_selected_search_uses_planes():
    """End-to-end engine.search with device planes registered: the
    selected-samples leaf answers identically to a plane-less engine."""
    from sbeacon_tpu.config import BeaconConfig, EngineConfig
    from sbeacon_tpu.engine import VariantEngine

    rng = random.Random(47)
    recs = random_records(
        rng, chrom="7", n=250, n_samples=6, p_no_acan=0.5
    )
    names = [f"S{i}" for i in range(6)]
    shard = build_index(
        recs, dataset_id="pk2", vcf_location="v", sample_names=names
    )

    def engine_with(device_planes):
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(
                    use_mesh=False,
                    microbatch=False,
                    device_planes=device_planes,
                )
            )
        )
        eng.add_index(shard)
        return eng

    e_dev = engine_with(True)
    e_host = engine_with(False)
    assert e_dev._indexes[("pk2", "v")][2] is not None
    assert e_host._indexes[("pk2", "v")][2] is None
    pos = shard.cols["pos"]
    for t in range(10):
        p = int(pos[rng.randrange(len(pos))])
        payload = VariantQueryPayload(
            dataset_ids=["pk2"],
            reference_name="7",
            start_min=max(1, p - 100),
            start_max=p + 100,
            end_min=1,
            end_max=1 << 30,
            alternate_bases="N",
            requested_granularity="record",
            include_datasets="HIT",
            include_samples=True,
            selected_samples_only=True,
            sample_names={"pk2": [names[i] for i in (0, 2, 5)]},
        )
        assert e_dev.search(payload) == e_host.search(payload), f"t={t}"
    e_dev.close()
    e_host.close()


def test_plane_budget_gate():
    """A plane set over the HBM budget stays host-resident (no device
    upload, fallback path serves)."""
    from sbeacon_tpu.config import BeaconConfig, EngineConfig
    from sbeacon_tpu.engine import VariantEngine

    rng = random.Random(53)
    recs = random_records(rng, chrom="7", n=50, n_samples=4)
    shard = build_index(
        recs, dataset_id="pk3", vcf_location="v", sample_names=list("ABCD")
    )
    eng = VariantEngine(
        BeaconConfig(
            engine=EngineConfig(
                use_mesh=False,
                microbatch=False,
                plane_hbm_budget_gb=1e-9,
            )
        )
    )
    eng.add_index(shard)
    assert eng._indexes[("pk3", "v")][2] is None
    eng.close()


# -- the resident layout: whole 128-lane rows, k narrow rows to each ----------


def _popcount_rows(words: np.ndarray) -> np.ndarray:
    """Set bits per row of a uint32 [r, W] block."""
    return np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), axis=1
    ).sum(axis=1, dtype=np.int64)


def _layout_shard(n_words: int, with_counts: bool):
    from sbeacon_tpu.index.columnar import FLAG
    from sbeacon_tpu.testing import synthetic_shard

    # the last word is partly filled wherever the width allows it
    n_samples = 32 * n_words - (5 if n_words > 1 else 23)
    shard = synthetic_shard(
        1501,
        n_samples=n_samples,
        seed=100 + n_words,
        dataset_id="lay",
        chroms=["7"],
        p_multiallelic=0.3,
        with_gt_planes=True,
        plane_density=0.06,
    )
    if with_counts:
        # genotype-derived rows: their counts come from the planes
        shard.cols["flags"][::3] &= ~np.int32(FLAG.AC_INFO | FLAG.AN_INFO)
    return shard


def _unpacked(resident: np.ndarray, n_rows: int, n_words: int):
    """A resident array read back as ``[n_rows, padded_words]`` rows and
    the zero rows that fill its last lane row."""
    from sbeacon_tpu.ops.plane_kernel import padded_words

    rows = resident.reshape(-1, padded_words(n_words))
    return rows[:n_rows], rows[n_rows:]


@pytest.mark.parametrize("with_counts", [False, True])
@pytest.mark.parametrize("n_words", [1, 20, 32, 33, 64, 79, 128, 129])
def test_resident_layout_answers_as_the_host_planes(n_words, with_counts):
    """The planes are resident in whole 128-lane rows: a row of over 64
    words zero-padded to them, k = 128 // p narrower rows sharing one
    (p the least power of two that holds a row; the row count is no
    multiple of k here). The fused program and ``plane_row_stats``
    still return exactly what plain numpy reads from the host planes,
    ``or_words`` as wide as the mask, through padding slots and a
    second chunk, and the same words as the k = 1 layout of the same
    planes (widened with zero words) returns."""
    from sbeacon_tpu.engine import host_match_rows
    from sbeacon_tpu.index.columnar import FLAG
    from sbeacon_tpu.ops.plane_kernel import (
        PlaneDeviceIndex,
        pack_factor,
        plane_row_stats,
        resident_shape,
        sample_mask_words,
    )
    from sbeacon_tpu.ops.scatter_kernel import (
        ScatterDeviceIndex,
        run_selected_scattered,
    )

    shard = _layout_shard(n_words, with_counts)
    assert shard.gt_bits.shape[1] == n_words
    k = pack_factor(n_words)
    assert k == {1: 128, 20: 4, 32: 4, 33: 2, 64: 2}.get(n_words, 1)
    assert k == 1 or shard.n_rows % k
    pindex = PlaneDeviceIndex(shard, upload_chunk_bytes=100 * n_words * 4)
    assert pindex.has_counts == with_counts
    assert pindex.n_words == n_words
    held = pindex.planes()
    assert len(held) == (4 if with_counts else 1)
    lane_rows, lanes = resident_shape(shard.n_rows, n_words)
    whole = -(-shard.n_rows // 128) * 128  # rows in whole steps
    assert lanes % 128 == 0 and lane_rows == whole // k
    for a in held:
        assert a.shape == (lane_rows, lanes)
    assert pindex.nbytes_hbm() == sum(int(a.nbytes) for a in held)
    assert pindex.nbytes_hbm() == PlaneDeviceIndex.estimate_hbm(shard)
    assert pindex.logical_bytes() == shard.n_rows * n_words * 4 * len(held)
    got, beyond = _unpacked(np.asarray(pindex.gt), shard.n_rows, n_words)
    np.testing.assert_array_equal(
        got[:, :n_words].view(np.uint32), shard.gt_bits
    )
    assert not got[:, n_words:].any() and not beyond.any()

    rng = random.Random(7 * n_words + with_counts)
    n_samples = len(shard.meta["sample_names"])
    pos = shard.cols["pos"]
    specs, masks = [], []
    for _ in range(70):  # one full chunk of 64 slots, one of 6 + padding
        i = rng.randrange(len(pos))
        j = min(len(pos) - 1, i + rng.randint(0, 40))
        specs.append(
            QuerySpec(
                "7", int(pos[i]), int(pos[j]), 1, 1 << 30,
                alternate_bases=rng.choice(["N", "N", "T"]),
            )
        )
        masks.append(
            np.full(n_words, 0xFFFFFFFF, np.uint32)
            if rng.random() < 0.2
            else sample_mask_words(
                rng.sample(range(n_samples), min(n_samples, 7)), n_words
            )
        )
    masks = np.stack(masks)
    res = run_selected_scattered(
        ScatterDeviceIndex(shard), pindex, specs, masks,
        window_cap=512, record_cap=64,
    )
    assert res.or_words.shape == (len(specs), n_words)
    if k > 1:
        # the same planes one row to a lane row: every word as above
        wide = 79 - n_words
        unpacked = dataclasses.replace(shard, **{
            name: np.pad(getattr(shard, name), ((0, 0), (0, wide)))
            for name in ("gt_bits", "gt_bits2", "tok_bits1", "tok_bits2")
        })
        one_a_row = PlaneDeviceIndex(unpacked)
        assert one_a_row.gt.shape == (whole, 128)
        ref = run_selected_scattered(
            ScatterDeviceIndex(shard), one_a_row, specs,
            np.pad(masks, ((0, 0), (0, wide))),
            window_cap=512, record_cap=64,
        )
        np.testing.assert_array_equal(res.rows, ref.rows)
        np.testing.assert_array_equal(res.pc_call, ref.pc_call)
        np.testing.assert_array_equal(res.pc_tok, ref.pc_tok)
        np.testing.assert_array_equal(res.or_words, ref.or_words[:, :n_words])
        assert not ref.or_words[:, n_words:].any()
    flags, ac = shard.cols["flags"], shard.cols["ac"]
    checked = 0
    assert not res.overflow.any()
    for q, spec in enumerate(specs):
        rows = host_match_rows(shard, spec)
        keep = res.rows[q] >= 0
        np.testing.assert_array_equal(res.rows[q][keep], rows)
        m = masks[q]
        pc_call = _popcount_rows(shard.gt_bits[rows] & m)
        pc_tok = np.zeros(len(rows), np.int64)
        rc = ac[rows].astype(np.int64)
        if with_counts:
            pc_call += _popcount_rows(shard.gt_bits2[rows] & m)
            pc_tok = _popcount_rows(
                shard.tok_bits1[rows] & m
            ) + _popcount_rows(shard.tok_bits2[rows] & m)
            rc = np.where(flags[rows] & FLAG.AC_INFO, rc, pc_call)
        np.testing.assert_array_equal(res.pc_call[q][keep], pc_call)
        np.testing.assert_array_equal(res.pc_tok[q][keep], pc_tok)
        # sample hits: every row of the records from the first one at
        # which the running count turns positive
        want_or = np.zeros(n_words, np.uint32)
        if len(rows) and rc.sum() > 0:
            rec = shard.cols["rec_id"][rows]
            first_row = int(np.argmax(np.cumsum(rc) > 0))
            sel = rows[rec >= rec[first_row]]
            want_or = np.bitwise_or.reduce(shard.gt_bits[sel] & m, axis=0)
        np.testing.assert_array_equal(res.or_words[q], want_or)

        or_sel = np.zeros(len(rows), np.int32)
        or_sel[::2] = 1
        counts, ow = plane_row_stats(pindex, rows, m, or_sel=or_sel)
        assert ow.shape == (n_words,)
        want = [_popcount_rows(shard.gt_bits[rows] & m)]
        for name in ("gt_bits2", "tok_bits1", "tok_bits2"):
            want.append(
                _popcount_rows(getattr(shard, name)[rows] & m)
                if with_counts
                else np.zeros(len(rows), np.int64)
            )
        np.testing.assert_array_equal(counts, np.stack(want, axis=1))
        np.testing.assert_array_equal(
            ow,
            np.bitwise_or.reduce(
                shard.gt_bits[rows[::2]] & m, axis=0,
                initial=np.uint32(0),
            ),
        )
        checked += 1
    assert checked == len(specs)
