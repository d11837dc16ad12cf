"""Kernel <-> CPU-oracle parity: the core correctness guarantee.

The oracle implements the reference's exact matching semantics
(performQuery/search_variants.py); the TPU kernel must agree on
exists/call_count/all_alleles_count/n_variants and on the matched row set
for every query shape Beacon v2 can produce.
"""

import random

import numpy as np
import pytest

from sbeacon_tpu.index import build_index
from sbeacon_tpu.oracle import oracle_search
from sbeacon_tpu.ops import DeviceIndex, QuerySpec, run_queries
from sbeacon_tpu.ops.kernel import LANES, FusedDeviceIndex, encode_queries
from sbeacon_tpu.testing import random_records


@pytest.fixture(scope="module")
def dataset():
    rng = random.Random(99)
    recs = random_records(
        rng, chrom="1", n=800, n_samples=6, p_symbolic=0.15, p_multiallelic=0.3
    )
    recs += random_records(rng, chrom="22", n=400, n_samples=6, p_symbolic=0.1)
    shard = build_index(recs, dataset_id="ds0", sample_names=[f"S{i}" for i in range(6)])
    dindex = DeviceIndex(shard, pad_unit=1024)
    return recs, shard, dindex


def _oracle(recs, q: QuerySpec):
    chrom_recs = [r for r in recs if r.chrom == q.chrom]
    return oracle_search(
        chrom_recs,
        first_bp=q.start_min,
        last_bp=q.start_max,
        end_min=q.end_min,
        end_max=q.end_max,
        reference_bases=q.reference_bases,
        alternate_bases=q.alternate_bases,
        variant_type=q.variant_type,
        variant_min_length=q.variant_min_length,
        variant_max_length=q.variant_max_length,
        requested_granularity="record",
        include_details=True,
    )


def _assert_parity(recs, shard, dindex, queries):
    res = run_queries(dindex, queries, window_cap=2048, record_cap=512)
    for i, q in enumerate(queries):
        want = _oracle(recs, q)
        assert not res.overflow[i], f"q{i} overflowed the window"
        assert bool(res.exists[i]) == want.exists, f"q{i} exists {q}"
        assert int(res.call_count[i]) == want.call_count, f"q{i} call_count {q}"
        assert (
            int(res.all_alleles_count[i]) == want.all_alleles_count
        ), f"q{i} all_alleles {q}"
        # matched rows with ac != 0 <=> oracle 'variants' entries
        rows = [r for r in res.rows[i] if r >= 0]
        got_variants = sorted(
            shard.variant_string(r, chrom_label=q.chrom)
            for r in rows
            if shard.cols["ac"][r] != 0
        )
        assert got_variants == sorted(want.variants), f"q{i} variants {q}"


def test_point_queries_exact_alt(dataset):
    recs, shard, dindex = dataset
    rng = random.Random(0)
    queries = []
    # half aimed at real variants, half at nothing
    targets = rng.sample([r for r in recs if r.chrom == "1"], 40)
    for r in targets:
        alt = r.alts[0]
        queries.append(
            QuerySpec(
                chrom="1",
                start_min=r.pos,
                start_max=r.pos,
                end_min=r.pos,
                end_max=r.pos + len(r.ref) + 5,
                reference_bases=r.ref.upper(),
                alternate_bases=alt.upper() if not alt.startswith("<") else "G",
            )
        )
        queries.append(
            QuerySpec(
                chrom="1",
                start_min=r.pos + 1,
                start_max=r.pos + 1,
                end_min=0,
                end_max=10**9,
                reference_bases="N",
                alternate_bases="G",
            )
        )
    _assert_parity(recs, shard, dindex, queries)


def test_range_and_bracket_queries(dataset):
    recs, shard, dindex = dataset
    rng = random.Random(1)
    c1 = [r for r in recs if r.chrom == "1"]
    queries = []
    for _ in range(30):
        a = rng.choice(c1).pos
        b = a + rng.randint(10, 3000)
        queries.append(
            QuerySpec(
                chrom=rng.choice(["1", "22"]),
                start_min=a,
                start_max=b,
                end_min=a,
                end_max=b + rng.randint(0, 2000),
                reference_bases="N",
                alternate_bases="N",
            )
        )
        # tight end-range bracket
        queries.append(
            QuerySpec(
                chrom="1",
                start_min=a,
                start_max=b,
                end_min=a + 5,
                end_max=a + 100,
                reference_bases=None,
                alternate_bases="N",
            )
        )
    _assert_parity(recs, shard, dindex, queries)


def test_variant_type_queries(dataset):
    recs, shard, dindex = dataset
    rng = random.Random(2)
    c1 = [r for r in recs if r.chrom == "1"]
    queries = []
    for vt in ["DEL", "INS", "DUP", "DUP:TANDEM", "CNV", "INV", "BND"]:
        for _ in range(8):
            a = rng.choice(c1).pos - rng.randint(0, 500)
            queries.append(
                QuerySpec(
                    chrom="1",
                    start_min=max(1, a),
                    start_max=a + 4000,
                    end_min=0,
                    end_max=10**9,
                    reference_bases="N",
                    alternate_bases=None,
                    variant_type=vt,
                )
            )
    _assert_parity(recs, shard, dindex, queries)


def test_length_filters(dataset):
    recs, shard, dindex = dataset
    rng = random.Random(3)
    c1 = [r for r in recs if r.chrom == "1"]
    queries = []
    for _ in range(20):
        a = rng.choice(c1).pos - 200
        lo = rng.randint(0, 3)
        queries.append(
            QuerySpec(
                chrom="1",
                start_min=max(1, a),
                start_max=a + 5000,
                end_min=0,
                end_max=10**9,
                reference_bases="N",
                alternate_bases="N" if rng.random() < 0.5 else None,
                variant_type="DEL" if rng.random() < 0.5 else "INS",
                variant_min_length=lo,
                variant_max_length=rng.choice([-1, lo + rng.randint(0, 4)]),
            )
        )
    _assert_parity(recs, shard, dindex, queries)


def test_ref_exact_match(dataset):
    recs, shard, dindex = dataset
    rng = random.Random(4)
    c1 = [r for r in recs if r.chrom == "1"]
    queries = []
    for _ in range(25):
        r = rng.choice(c1)
        ref = r.ref if rng.random() < 0.7 else "ACGTACGT"  # mostly real refs
        queries.append(
            QuerySpec(
                chrom="1",
                start_min=r.pos - 50,
                start_max=r.pos + 50,
                end_min=0,
                end_max=10**9,
                reference_bases=ref.upper(),
                alternate_bases="N",
            )
        )
    _assert_parity(recs, shard, dindex, queries)


def test_empty_and_unknown_chrom(dataset):
    recs, shard, dindex = dataset
    queries = [
        QuerySpec(chrom="9", start_min=1, start_max=10**6, end_min=0, end_max=10**9,
                  reference_bases="N", alternate_bases="N"),
        QuerySpec(chrom="1", start_min=10**8, start_max=10**8 + 10, end_min=0,
                  end_max=10**9, reference_bases="N", alternate_bases="N"),
    ]
    res = run_queries(dindex, queries)
    assert not res.exists.any()
    assert (res.rows == -1).all()


def test_genotype_fallback_records(dataset):
    """Records without INFO AC/AN use genotype-derived counts — parity holds
    because ingest materialises the same numbers the oracle computes."""
    recs, shard, dindex = dataset
    no_acan = [r for r in recs if r.ac is None and r.chrom == "1"]
    assert no_acan, "fixture should contain AC/AN-less records"
    queries = [
        QuerySpec(
            chrom="1",
            start_min=r.pos,
            start_max=r.pos,
            end_min=0,
            end_max=10**9,
            reference_bases="N",
            alternate_bases="N",
        )
        for r in no_acan[:20]
    ]
    _assert_parity(recs, shard, dindex, queries)


def test_window_overflow_flagged():
    rng = random.Random(5)
    recs = random_records(rng, chrom="1", n=600, spacing=2, n_samples=2)
    shard = build_index(recs, sample_names=["a", "b"])
    dindex = DeviceIndex(shard, pad_unit=1024)
    q = QuerySpec(
        chrom="1", start_min=1, start_max=10**7, end_min=0, end_max=10**9,
        reference_bases="N", alternate_bases="N",
    )
    res = run_queries(dindex, [q], window_cap=64)
    assert res.overflow[0]


def test_int32_max_start_max_does_not_wrap(dataset):
    """start_max=INT32_MAX (the unbounded sentinel) must not overflow the
    device-side upper-bound search (regression: lower_bound(target+1) wrapped
    to INT32_MIN and returned zero matches with no overflow flag)."""
    records, shard, dindex = dataset
    from sbeacon_tpu.engine import host_match_rows

    spec = QuerySpec(
        chrom="1",
        start_min=1,
        start_max=2**31 - 1,
        end_min=1,
        end_max=2**30,
        alternate_bases="N",
    )
    res = run_queries(dindex, [spec], window_cap=8192, record_cap=4096)
    want = host_match_rows(shard, spec)
    assert not res.overflow[0]
    assert int(res.n_matched[0]) == len(want)
    assert len(want) > 0


# --- the window read in lane rows, at its edges -----------------------

EDGE_CAP = 256  # window_cap of the edge cases: three lane rows


@pytest.fixture(scope="module")
def stack():
    """Three datasets on chromosome 1, the middle one smaller than a
    window, none beginning at a lane row's first lane; the stack ends
    inside its last lane row (4093 rows padded to 4096)."""
    shards, all_recs = [], []
    for d, n_rows in enumerate((1700, 150, 2243)):
        rng = random.Random(40 + d)
        recs = random_records(
            rng, chrom="1", n=n_rows, spacing=9, n_samples=3,
            p_multiallelic=0.0,
        )
        for rec in recs:  # four rows of five match any single base
            if rng.random() < 0.8:
                rec.alts = [rng.choice([b for b in "ACGT" if b != rec.ref])]
        shards.append(build_index(recs, dataset_id=f"d{d}", sample_names=["a", "b", "c"]))
        all_recs.append(recs)
    findex = FusedDeviceIndex(shards, pad_unit=1024)
    assert [s.n_rows for s in shards] == [1700, 150, 2243]
    assert findex.n_padded == 4096 and findex.window_hint >= 2048
    return all_recs, shards, findex


def _rows_query(shard, a, count, **kw):
    """The any-single-base query whose candidate range is rows
    [a, a + count) of a shard with one row a position."""
    pos = shard.cols["pos"]
    if count == 0:  # between two adjacent rows' positions: nothing
        gap = next(
            r for r in range(a, shard.n_rows - 1) if pos[r + 1] - pos[r] > 2
        )
        lo_bp = hi_bp = int(pos[gap]) + 1
    else:
        lo_bp, hi_bp = int(pos[a]), int(pos[a + count - 1])
    return QuerySpec(
        chrom="1", start_min=lo_bp, start_max=hi_bp, end_min=1,
        end_max=2**30, alternate_bases="N", **kw,
    )


# (case, dataset, first row as a function of the dataset's base row in
# the stack, rows in the range, record_cap)
WINDOW_EDGES = [
    ("lo_inside_a_lane_row", 0, lambda base: 300 + 37, 90, 512),
    ("lo_on_a_lane_row", 2, lambda base: 3 * LANES - base % LANES, 90, 512),
    ("lo_on_a_rows_last_lane", 0, lambda base: 5 * LANES - 1, 200, 512),
    ("first_row_of_the_stack", 0, lambda base: 0, 40, 512),
    ("ends_in_the_stacks_last_lane_row", 2, lambda base: 2243 - 70, 70, 512),
    ("range_equal_to_window_cap", 0, lambda base: 911, EDGE_CAP, 512),
    ("range_one_over_window_cap", 0, lambda base: 911, EDGE_CAP + 1, 512),
    ("range_far_over_window_cap", 2, lambda base: 77, 1500, 512),
    ("a_whole_dataset_between_neighbours", 1, lambda base: 0, 150, 512),
    ("ends_at_the_next_datasets_first_row", 0, lambda base: 1700 - 30, 30, 512),
    ("begins_at_the_previous_datasets_last_row", 2, lambda base: 0, 30, 512),
    ("empty_window", 1, lambda base: 60, 0, 512),
    ("record_cap_under_the_matches", 2, lambda base: 1000, 120, 16),
    ("record_cap_over_the_window", 0, lambda base: 450, 60, 1024),
]


@pytest.mark.parametrize(
    "case,sid,first,count,record_cap",
    WINDOW_EDGES,
    ids=[c[0] for c in WINDOW_EDGES],
)
def test_window_read_in_lane_rows(stack, case, sid, first, count, record_cap):
    """The fused program against the CPU oracle where a window meets a
    lane row's, a dataset's, the stack's or its own cap's edge."""
    from sbeacon_tpu.engine import host_match_rows

    all_recs, shards, findex = stack
    shard, base = shards[sid], int(findex.shard_base[sid])
    a = first(base)
    q = _rows_query(shard, a, count)
    res = run_queries(
        findex,
        encode_queries([q], shard_ids=[sid]),
        window_cap=EDGE_CAP,
        record_cap=record_cap,
    )
    pos = shard.cols["pos"]
    lo = int(np.searchsorted(pos, q.start_min, side="left"))
    hi = int(np.searchsorted(pos, q.start_max, side="right"))
    assert hi - lo == count and (lo == a or not count)
    want_rows = host_match_rows(shard, q) + base
    assert len(want_rows) > count // 2 or not count
    in_window = want_rows[want_rows < base + lo + EDGE_CAP]
    assert bool(res.overflow[0]) == (count > EDGE_CAP)
    assert int(res.n_matched[0]) == len(in_window)
    got = res.rows[0]
    assert got.shape == (min(record_cap, EDGE_CAP),)
    kept = in_window[: len(got)]
    assert got[: len(kept)].tolist() == kept.tolist()
    assert (got[len(kept):] == -1).all()
    ac = shard.cols["ac"][in_window - base]
    assert int(res.call_count[0]) == int(ac.sum())
    assert int(res.n_variants[0]) == int((ac != 0).sum())
    if count <= EDGE_CAP:
        want = _oracle(all_recs[sid], q)
        assert bool(res.exists[0]) == want.exists
        assert int(res.call_count[0]) == want.call_count
        assert int(res.all_alleles_count[0]) == want.all_alleles_count
