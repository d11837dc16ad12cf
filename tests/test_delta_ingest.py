"""Ingest-while-serving (ISSUE 10): immediate delta-shard publication,
background compaction, and region/dataset-scoped cache invalidation.

The write-path contract under test:

- a submitted variant is queryable the moment its slice/delta publishes
  (read-your-writes before any compaction),
- a delta publish does NOT demolish the query plane: the base
  fingerprint (and therefore the fused/mesh stacks) stays warm, and
  only cache entries whose dataset AND
  region overlap the new rows are evicted — a cached negative for an
  overlapping bracket is the critical kill,
- base + delta serving is bit-equal (at the aggregate level each
  granularity exposes) to a freshly rebuilt monolith,
- a crashed compaction changes nothing observable and the next run
  completes the fold.
"""

import random
import threading
import time
from pathlib import Path

import jax
import pytest

from sbeacon_tpu.config import (
    BeaconConfig,
    EngineConfig,
    IngestConfig,
    StorageConfig,
)
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.genomics.tabix import ensure_index
from sbeacon_tpu.genomics.vcf import VcfRecord, write_vcf
from sbeacon_tpu.harness import faults
from sbeacon_tpu.index.columnar import build_index, merge_shards
from sbeacon_tpu.ingest.ledger import JobLedger
from sbeacon_tpu.ingest.pipeline import (
    SLICE_DISK,
    SummarisationPipeline,
)
from sbeacon_tpu.ingest.service import DeltaCompactor
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records

pytestmark = pytest.mark.ingest

SAMPLES = ["S0", "S1"]


def _rec(chrom: str, pos: int, ref: str = "A", alt: str = "T") -> VcfRecord:
    return VcfRecord(
        chrom=chrom,
        pos=pos,
        ref=ref,
        alts=[alt],
        ac=[1],
        an=4,
        vt="SNP",
        genotypes=["0|1", "0|0"],
    )


def _shard(records, ds="dsA", vcf="a.vcf"):
    return build_index(
        records, dataset_id=ds, vcf_location=vcf, sample_names=SAMPLES
    )


def _engine(*shards, **eng_over) -> VariantEngine:
    eng_over.setdefault("use_mesh", False)
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(**eng_over)))
    for s in shards:
        eng.add_index(s)
    return eng


def _bracket(chrom="1", lo=1, hi=1 << 29, datasets=(), gran="count",
             include="HIT", alt="N"):
    return VariantQueryPayload(
        dataset_ids=list(datasets),
        reference_name=chrom,
        start_min=lo,
        start_max=hi,
        end_min=lo,
        end_max=hi + 64,
        alternate_bases=alt,
        requested_granularity=gran,
        include_datasets=include,
    )


def _variants(responses) -> set:
    return {v for r in responses for v in r.variants}


def _compactor(engine, tmp_path, **ingest_over) -> DeltaCompactor:
    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "data"),
        ingest=IngestConfig(**ingest_over),
    )
    cfg.storage.ensure()
    pipe = SummarisationPipeline(cfg, ledger=JobLedger(), engine=engine)
    return DeltaCompactor(engine, pipe, pipe.ledger, cfg)


# -- read-your-writes ---------------------------------------------------------


def test_delta_publish_is_immediately_queryable(tmp_path):
    """A variant arriving as a delta answers the next search — before
    any compaction, with the base shard untouched."""
    eng = _engine(_shard(random_records(random.Random(1), chrom="1",
                                        n=80, n_samples=2)))
    try:
        miss = eng.search(_bracket(chrom="2"))
        assert not any(r.exists for r in miss)
        t0 = time.perf_counter()
        eng.add_delta(_shard([_rec("2", 777)], vcf="a.vcf"))
        hit = eng.search(_bracket(chrom="2"))
        lag_s = time.perf_counter() - t0
        assert any(r.exists for r in hit)
        assert any("777" in v for v in _variants(hit))
        # read-your-writes freshness: publish -> first hit well under
        # the 1 s acceptance bound (no rebuild in the path)
        assert lag_s < 1.0, f"delta->hit took {lag_s:.2f}s"
        assert eng.delta_stats()["dsA"]["shards"] == 1
    finally:
        eng.close()


def test_streamed_summarisation_queryable_before_base_publish(tmp_path):
    """The pipeline's streaming mode: slices publish as deltas during
    the scan; with deferred base publish the data serves BEFORE any
    base shard exists for the key (compaction later folds it)."""
    rng = random.Random(3)
    recs = random_records(rng, chrom="1", n=400, n_samples=2)
    vcf = tmp_path / "s.vcf.gz"
    write_vcf(vcf, recs, sample_names=SAMPLES)
    ensure_index(vcf)
    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "data"),
        engine=EngineConfig(use_mesh=False),
        ingest=IngestConfig(
            min_task_time=1e-6,
            scan_rate=1e6,
            dispatch_cost=1e-7,
            max_concurrency=1000,
            workers=2,
            stream_deltas=True,
            defer_base_publish=True,
            compact_interval_s=0.0,
        ),
    )
    cfg.storage.ensure()
    eng = VariantEngine(cfg)
    pipe = SummarisationPipeline(cfg, ledger=JobLedger(), engine=eng)
    try:
        stats = pipe.summarise_dataset("dsA", [str(vcf)])
        assert stats["callCount"] > 0
        # base publish deferred: no base shard, a standing delta tail
        assert not eng.has_index("dsA", str(vcf))
        assert eng.delta_stats()["dsA"]["shards"] >= 1
        got = eng.search(_bracket(chrom="1", alt="N"))
        want = {r.pos for r in recs
                if any(len(a) == 1 and a.upper() in "ACGTN"
                       for a in r.alts)}
        assert any(r.exists for r in got) == bool(want)
        # fold through the compactor: identical answers, empty tail
        pre = _variants(eng.search(_bracket(chrom="1")))
        comp = DeltaCompactor(eng, pipe, pipe.ledger, cfg)
        folded = comp.run_once()
        assert ("dsA", str(vcf)) in folded
        assert eng.has_index("dsA", str(vcf))
        assert eng.delta_stats() == {}
        assert _variants(eng.search(_bracket(chrom="1"))) == pre
    finally:
        eng.close()


# -- scoped cache invalidation ------------------------------------------------


def test_negative_cache_evicted_by_overlapping_delta():
    """THE correctness case: a cached 'no' for a bracket must die the
    moment a variant lands inside it."""
    eng = _engine(_shard([_rec("1", 1000)]))
    try:
        neg = _bracket(chrom="1", lo=5000, hi=6000)
        assert not any(r.exists for r in eng.search(neg))
        assert not any(r.exists for r in eng.search(neg))  # cached no
        assert eng.cache_stats()["negative_hits"] == 1
        eng.add_delta(_shard([_rec("1", 5500)], vcf="a.vcf"))
        got = eng.search(neg)
        assert any(r.exists for r in got), (
            "cached negative survived an overlapping delta publish"
        )
    finally:
        eng.close()


def test_nonoverlapping_entries_survive_delta_publish():
    """A delta publish evicts ONLY overlapping entries: other regions,
    other chromosomes and other datasets keep their warm hits."""
    sA = _shard(
        [_rec("1", 1000), _rec("2", 1000)], ds="dsA", vcf="a.vcf"
    )
    sB = _shard([_rec("1", 1000)], ds="dsB", vcf="b.vcf")
    eng = _engine(sA, sB)
    try:
        q_far = _bracket(chrom="1", lo=900, hi=1100, datasets=["dsA"])
        q_chr2 = _bracket(chrom="2", lo=900, hi=1100, datasets=["dsA"])
        q_dsB = _bracket(chrom="1", lo=1, hi=1 << 29, datasets=["dsB"])
        for q in (q_far, q_chr2, q_dsB):
            eng.search(q)  # prime
        hits0 = eng.cache_stats()["hits"]
        # delta for dsA chr1 FAR from q_far's bracket
        eng.add_delta(_shard([_rec("1", 500_000)], ds="dsA",
                             vcf="a.vcf"))
        # non-overlapping entries still hit...
        for q in (q_chr2, q_dsB, q_far):
            eng.search(q)
        assert eng.cache_stats()["hits"] == hits0 + 3
        # ...and an overlapping bracket sees the new variant
        q_cover = _bracket(chrom="1", lo=400_000, hi=600_000,
                           datasets=["dsA"])
        assert any("500000" in v
                   for v in _variants(eng.search(q_cover)))
    finally:
        eng.close()


def test_all_dataset_entries_scope_evicted_by_region():
    """Entries for dataset_ids=[] (every dataset) overlap any dataset's
    publish — but still survive when the REGION is disjoint."""
    eng = _engine(_shard([_rec("1", 1000)]))
    try:
        q_all_chr2 = _bracket(chrom="2")
        eng.search(q_all_chr2)
        hits0 = eng.cache_stats()["hits"]
        eng.add_delta(_shard([_rec("1", 2000)], vcf="a.vcf"))
        eng.search(q_all_chr2)  # chr2 bracket: disjoint from chr1 delta
        assert eng.cache_stats()["hits"] == hits0 + 1
        q_all_chr1 = _bracket(chrom="1")
        assert any("2000" in v
                   for v in _variants(eng.search(q_all_chr1)))
    finally:
        eng.close()


def test_scoped_invalidation_toggle_off_restores_wholesale_clear():
    eng = _engine(
        _shard([_rec("1", 1000)]), scoped_invalidation=False
    )
    try:
        eng.search(_bracket(chrom="2"))
        assert eng.cache_stats()["entries"] == 1
        eng.add_delta(_shard([_rec("1", 9000)], vcf="a.vcf"))
        stats = eng.cache_stats()
        assert stats["entries"] == 0  # wholesale clear
        assert stats["scoped_invalidations"] == 0
    finally:
        eng.close()


def test_put_race_guard_refuses_stale_store():
    """A search that raced an overlapping invalidation must not store
    its pre-publish result; a non-overlapping racer may."""
    from sbeacon_tpu.response_cache import ResponseCache

    cache = ResponseCache()
    gen = cache.generation()
    cache.invalidate_scope(["dsA"], "1", (100, 200))
    scope_overlap = (frozenset({"dsA"}), "1", (150, 250))
    scope_clear = (frozenset({"dsB"}), "2", (1, 50))
    assert cache.put(("k1",), [], scope=scope_overlap, gen=gen) is False
    assert cache.put(("k2",), [], scope=scope_clear, gen=gen) is True
    assert cache.put(("k3",), [], scope=scope_overlap) is True  # no gen


# -- parity -------------------------------------------------------------------


def test_base_plus_delta_matches_monolith_across_granularities():
    rng = random.Random(11)
    recs = random_records(rng, chrom="1", n=300, n_samples=2)
    cut1, cut2 = len(recs) // 2, 3 * len(recs) // 4
    base = _shard(recs[:cut1])
    d1 = _shard(recs[cut1:cut2], vcf="a.vcf")
    d2 = _shard(recs[cut2:], vcf="a.vcf")
    split = _engine(base)
    split.add_delta(d1)
    split.add_delta(d2)
    mono = _engine(
        _shard(recs)
    )
    try:
        for gran in ("boolean", "count", "record"):
            for alt in (None, "N", "T"):
                q = _bracket(chrom="1", gran=gran, alt=alt,
                             include="HIT")
                rs, rm = split.search(q), mono.search(q)
                assert any(r.exists for r in rs) == any(
                    r.exists for r in rm
                ), (gran, alt)
                if gran == "boolean":
                    continue  # per-response truncation may differ
                assert _variants(rs) == _variants(rm), (gran, alt)
                assert sum(r.call_count for r in rs) == sum(
                    r.call_count for r in rm
                ), (gran, alt)
                assert sum(r.all_alleles_count for r in rs) == sum(
                    r.all_alleles_count for r in rm
                ), (gran, alt)
    finally:
        split.close()
        mono.close()


def test_compaction_preserves_answers_and_retires_tail(tmp_path):
    rng = random.Random(12)
    recs = random_records(rng, chrom="1", n=200, n_samples=2)
    eng = _engine(_shard(recs[:120]))
    eng.add_delta(_shard(recs[120:160], vcf="a.vcf"))
    eng.add_delta(_shard(recs[160:], vcf="a.vcf"))
    try:
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        base_fp = eng.base_fingerprint()
        comp = _compactor(eng, tmp_path)
        folded = comp.run_once()
        assert set(folded) == {("dsA", "a.vcf")}
        assert folded[("dsA", "a.vcf")] > 0
        assert eng.delta_stats() == {}
        assert eng.base_fingerprint() != base_fp
        assert _variants(eng.search(q)) == pre
        # tiered is the DEFAULT (ISSUE 20): this tail is large
        # relative to the base (80/120 rows >= the 0.35 byte ratio),
        # so ONE sweep runs both tiers — the L1 consolidation and the
        # ratio-triggered base merge
        assert comp.metrics()["runs"] == 2
        assert comp.metrics()["tier_folds"] == {"l1": 1, "base": 1}
    finally:
        eng.close()


# -- crash resilience ---------------------------------------------------------


@pytest.mark.resilience
def test_crashed_compaction_keeps_serving_then_completes(tmp_path):
    """An injected ``compaction.fold`` crash must leave base + deltas
    serving correct, duplicate-free results; the NEXT run completes
    the fold with identical answers."""
    rng = random.Random(13)
    recs = random_records(rng, chrom="1", n=150, n_samples=2)
    eng = _engine(_shard(recs[:100]))
    eng.add_delta(_shard(recs[100:], vcf="a.vcf"))
    try:
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        pre_calls = sum(r.call_count for r in eng.search(q))
        comp = _compactor(eng, tmp_path)
        faults.install(
            {
                "seed": 7,
                "rules": [
                    {
                        "site": "compaction.fold",
                        "kind": "error",
                        "rate": 1.0,
                        "count": 1,
                    }
                ],
            }
        )
        try:
            out = comp.run_once()
        finally:
            faults.uninstall()
        assert out == {}  # the fold crashed, nothing published
        assert comp.metrics()["failures"] == 1
        # base + deltas still serve, duplicate-free
        assert eng.delta_stats()["dsA"]["shards"] == 1
        assert _variants(eng.search(q)) == pre
        assert sum(r.call_count for r in eng.search(q)) == pre_calls
        # next run completes the fold
        folded = comp.run_once()
        assert ("dsA", "a.vcf") in folded
        assert eng.delta_stats() == {}
        assert _variants(eng.search(q)) == pre
        assert sum(r.call_count for r in eng.search(q)) == pre_calls
    finally:
        eng.close()


@pytest.mark.resilience
def test_crash_after_persist_before_publish_recovers(tmp_path):
    """The other side of the durability seam: merged artifact saved,
    engine swap crashed — deltas keep serving and the retry adopts the
    persisted artifact."""
    rng = random.Random(14)
    recs = random_records(rng, chrom="1", n=120, n_samples=2)
    eng = _engine(_shard(recs[:80]))
    eng.add_delta(_shard(recs[80:], vcf="a.vcf"))
    try:
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        comp = _compactor(eng, tmp_path)
        faults.install(
            {
                "seed": 7,
                "rules": [
                    {
                        "site": "compaction.fold",
                        "kind": "error",
                        "rate": 1.0,
                        "count": 1,
                        "match": ":publish",
                    }
                ],
            }
        )
        try:
            out = comp.run_once()
        finally:
            faults.uninstall()
        assert out == {}
        # the merged artifact IS on disk, but the swap never happened
        assert comp.pipeline.shard_path("dsA", "a.vcf").exists()
        assert eng.delta_stats()["dsA"]["shards"] == 1
        assert _variants(eng.search(q)) == pre
        folded = comp.run_once()
        assert ("dsA", "a.vcf") in folded
        assert _variants(eng.search(q)) == pre
    finally:
        eng.close()


# -- concurrency --------------------------------------------------------------


def test_concurrent_queries_during_continuous_ingest():
    """Queries racing a stream of delta publishes never error and end
    fully consistent once the stream stops."""
    eng = _engine(_shard([_rec("1", 100)]))
    errors: list = []
    stop = threading.Event()

    def publisher():
        for i in range(20):
            eng.add_delta(
                _shard([_rec("1", 10_000 + 100 * i)], vcf="a.vcf")
            )
            time.sleep(0.002)
        stop.set()

    def querier():
        while not stop.is_set():
            try:
                eng.search(_bracket(chrom="1"))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=publisher)] + [
        threading.Thread(target=querier) for _ in range(4)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:1]
        got = _variants(eng.search(_bracket(chrom="1")))
        want_pos = {100} | {10_000 + 100 * i for i in range(20)}
        assert {int(v.split("\t")[1]) for v in got} == want_pos
        assert eng.delta_stats()["dsA"]["shards"] == 20
    finally:
        eng.close()


# -- warm stacks across publishes --------------------------------------------


def test_fingerprint_split_and_epoch_monotonicity():
    eng = _engine(_shard([_rec("1", 1000)]))
    try:
        base_fp = eng.base_fingerprint()
        full_fp = eng.index_fingerprint()
        cache_other = eng.cache_fingerprint(["dsB"])
        eng.add_delta(_shard([_rec("1", 2000)], vcf="a.vcf"))
        assert eng.base_fingerprint() == base_fp
        assert eng.index_fingerprint() != full_fp
        assert eng.cache_fingerprint(["dsB"]) == cache_other
        # fold via a base publish carrying the folded epoch
        merged = merge_shards(
            [_shard([_rec("1", 1000)]),
             _shard([_rec("1", 2000)], vcf="a.vcf")]
        )
        merged.meta.update(
            dataset_id="dsA", vcf_location="a.vcf", delta_epoch=1
        )
        eng.add_index(merged)
        assert eng.delta_stats() == {}
        assert eng.base_fingerprint() != base_fp
        # epochs continue past the folded one (restart monotonicity)
        assert eng.add_delta(
            _shard([_rec("1", 3000)], vcf="a.vcf")
        ) == 2
    finally:
        eng.close()


def test_fused_stack_stays_clean_across_delta_publish():
    """The engine's fused cross-shard stack is NOT dirtied by a delta
    publish (base fingerprint stable) — and queries still see delta
    rows via the per-shard tail."""
    shards = [
        _shard(random_records(random.Random(20 + i), chrom="1", n=120,
                              n_samples=2),
               ds=f"d{i}", vcf=f"v{i}")
        for i in range(3)
    ]
    eng = _engine(*shards)
    try:
        eng.warmup()
        assert eng._fused_dirty is False
        eng.add_delta(_shard([_rec("1", 123_456)], ds="d0", vcf="v0"))
        assert eng._fused_dirty is False, (
            "delta publish dirtied the fused stack"
        )
        got = eng.search(
            _bracket(chrom="1", datasets=["d0", "d1", "d2"])
        )
        assert any("123456" in v for v in _variants(got))
    finally:
        eng.close()


# -- slice temp-disk ----------------------------------------------------------


def test_slice_files_deleted_as_folded_and_gauge_returns_to_zero(
    tmp_path,
):
    rng = random.Random(40)
    recs = []
    for chrom in ("1", "2", "3"):
        recs.extend(random_records(rng, chrom=chrom, n=900, n_samples=2))
    vcf = tmp_path / "big.vcf.gz"
    write_vcf(vcf, recs, sample_names=SAMPLES)
    ensure_index(vcf)
    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "data"),
        engine=EngineConfig(use_mesh=False),
        ingest=IngestConfig(
            min_task_time=1e-6,
            scan_rate=1e6,
            dispatch_cost=1e-7,
            max_concurrency=1000,
            workers=1,  # deterministic: one slice on disk at a time
            stream_deltas=True,
        ),
    )
    cfg.storage.ensure()
    eng = VariantEngine(cfg)
    pipe = SummarisationPipeline(cfg, ledger=JobLedger(), engine=eng)
    from sbeacon_tpu.ingest.planner import plan_slices

    plan = plan_slices(ensure_index(vcf), cfg.ingest)
    assert len(plan.slices) >= 3, "fixture must be multi-slice"
    SLICE_DISK.reset()
    try:
        pipe.summarise_dataset("dsA", [str(vcf)])
        stats = SLICE_DISK.stats()
        assert stats["current"] == 0  # everything folded + deleted
        assert stats["peak"] > 0
        # streaming + serial workers: slices die as they fold, so the
        # peak is far below the sum of all slices that existed
        final = pipe.shard_path("dsA", str(vcf))
        assert final.exists()
        assert not pipe._slice_dir("dsA", str(vcf)).exists()
    finally:
        eng.close()


# -- L0 delta-tail mini-index (ISSUE 15) --------------------------------------


def _deep_tail_engine(rng_seed=60, n=500, cut=300, n_deltas=5,
                      **eng_over):
    """Base + an ``n_deltas``-deep raw delta tail on one key, with the
    record set returned so parity twins can be built from it."""
    recs = random_records(random.Random(rng_seed), chrom="1", n=n,
                          n_samples=2)
    eng = _engine(_shard(recs[:cut]), **eng_over)
    step = (n - cut) // n_deltas
    for i in range(n_deltas):
        hi = cut + (i + 1) * step if i < n_deltas - 1 else n
        eng.add_delta(_shard(recs[cut + i * step:hi], vcf="a.vcf"))
    return eng, recs


def _cost_search(eng, payload):
    """(responses, CostVector) for one search under a fresh request
    context — the delta_shards attribution the satellite fix asserts."""
    from sbeacon_tpu.telemetry import RequestContext, request_context

    ctx = RequestContext(route="test")
    with request_context(ctx):
        responses = eng.search(payload)
    return responses, ctx.cost


def test_l0_stack_builds_past_threshold_and_serves_tail():
    """Past the tail-depth threshold the delta registry stacks the
    tail into the L0 mini-index; a deep-tail query then pays ZERO
    per-tail-shard host scans (the structural acceptance claim) and
    the launch lands in the fused_l0 recorder family."""
    from sbeacon_tpu.telemetry import flight_recorder

    eng, recs = _deep_tail_engine(l0_min_shards=3, response_cache=False)
    try:
        status = eng.l0_status()
        assert status["built"] and status["shards"] == 5
        fam0 = flight_recorder.launches_by_family().get("fused_l0", 0)
        got, cost = _cost_search(eng, _bracket(chrom="1"))
        assert cost.delta_shards == 0, (
            "L0-served tail targets must not charge host-scan units"
        )
        assert eng.l0_searches >= 1
        assert flight_recorder.launches_by_family()["fused_l0"] > fam0
        # answers match a monolith holding every row
        mono = _engine(_shard(recs))
        try:
            assert _variants(got) == _variants(
                mono.search(_bracket(chrom="1"))
            )
        finally:
            mono.close()
    finally:
        eng.close()


def test_l0_parity_byte_identical_across_shapes():
    """base+L0 vs base+host-scanned-tail (the same data, L0 on/off)
    must be byte-identical per response (dataclasses.asdict) across
    boolean/count/record x selected-samples shapes — and aggregate-
    equal to a monolith holding every row."""
    import dataclasses

    on, recs = _deep_tail_engine(rng_seed=61, l0_min_shards=3,
                                 response_cache=False)
    off, _ = _deep_tail_engine(rng_seed=61, l0_min_shards=0,
                               l0_min_rows=0, response_cache=False)
    mono = _engine(_shard(recs))
    try:
        assert on.l0_status()["built"] and not off.l0_status()["built"]
        payloads = []
        for gran in ("boolean", "count", "record"):
            for alt in (None, "N", "T"):
                payloads.append(_bracket(chrom="1", gran=gran, alt=alt))
        sel = _bracket(chrom="1", gran="record")
        sel.selected_samples_only = True
        sel.sample_names = {"dsA": ["S0"]}
        sel.include_samples = True
        payloads.append(sel)
        for q in payloads:
            a = [dataclasses.asdict(r) for r in on.search(q)]
            b = [dataclasses.asdict(r) for r in off.search(q)]
            assert a == b, (q.requested_granularity, q.alternate_bases)
            if q.requested_granularity == "boolean":
                continue
            rm = mono.search(q)
            assert _variants(on.search(q)) == _variants(rm)
            assert sum(r.call_count for r in on.search(q)) == sum(
                r.call_count for r in rm
            )
    finally:
        on.close()
        off.close()
        mono.close()


def test_l0_generation_retired_by_fold_and_residue_still_charged(
    tmp_path,
):
    """A base publish retires the covered L0 generation in the SAME
    critical section that drops the delta epochs (rows never doubled
    or missing), and a later sub-threshold residue charges exactly
    its host-walked shard count."""
    eng, recs = _deep_tail_engine(l0_min_shards=3, response_cache=False)
    try:
        assert eng.l0_status()["built"]
        pre = _variants(eng.search(_bracket(chrom="1")))
        comp = _compactor(eng, tmp_path)
        folded = comp.run_once()
        assert ("dsA", "a.vcf") in folded
        # the fold dropped the epochs AND the L0 coverage atomically:
        # no tail, no L0, answers identical (nothing doubled/missing)
        assert eng.delta_stats() == {}
        assert not eng.l0_status()["built"]
        assert _variants(eng.search(_bracket(chrom="1"))) == pre
        # a fresh sub-threshold delta is the host-scan residue: its
        # walk charges exactly one delta_shards unit
        eng.add_delta(_shard([_rec("1", 900_000)], vcf="a.vcf"))
        got, cost = _cost_search(eng, _bracket(chrom="1"))
        assert cost.delta_shards == 1
        assert any("900000" in v for v in _variants(got))
    finally:
        eng.close()


def test_delta_shard_charges_match_shards_actually_host_walked():
    """Satellite regression (cost attribution): with one key's tail
    L0-served and another key's tail below threshold, delta_shards
    charges count ONLY the host-walked residue; with L0 disabled the
    same state charges every tail shard."""
    def build(l0_shards):
        recs = random_records(random.Random(62), chrom="1", n=400,
                              n_samples=2)
        eng = _engine(
            _shard(recs[:200]),
            _shard(random_records(random.Random(63), chrom="1", n=100,
                                  n_samples=2), ds="dsB", vcf="b.vcf"),
            l0_min_shards=l0_shards,
            l0_min_rows=0 if l0_shards == 0 else 4096,
            response_cache=False,
        )
        step = 50
        for i in range(4):  # dsA: 4-deep tail (past threshold at 3)
            eng.add_delta(
                _shard(recs[200 + i * step:250 + i * step], vcf="a.vcf")
            )
        # dsB: 2-deep tail (below threshold — the residue)
        eng.add_delta(_shard([_rec("1", 700_001)], ds="dsB",
                             vcf="b.vcf"))
        eng.add_delta(_shard([_rec("1", 700_002)], ds="dsB",
                             vcf="b.vcf"))
        return eng

    on = build(3)
    off = build(0)
    try:
        q = _bracket(chrom="1")
        _got, cost = _cost_search(on, q)
        assert cost.delta_shards == 2, (
            "only dsB's host-walked residue may charge"
        )
        _got, cost = _cost_search(off, q)
        assert cost.delta_shards == 6, (
            "with L0 off every tail shard host-walks and charges"
        )
    finally:
        on.close()
        off.close()


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="the mesh stack needs >=2 devices (forced-host CI mesh)",
)
def test_coordinator_delta_tail_rides_l0_beside_the_mesh_launch():
    """Through a coordinator the local engine's own routes hold: the
    base rows of a multi-dataset count are ONE mesh launch, and a deep
    delta tail next to it is L0-served before anything falls to
    host_match_rows (zero delta_shards charges), the tail rows in the
    answers."""
    from sbeacon_tpu.parallel.dispatch import DistributedEngine
    from sbeacon_tpu.telemetry import (
        RequestContext,
        flight_recorder,
        request_context,
    )

    shards = [
        _shard(random_records(random.Random(64 + i), chrom="1", n=150,
                              n_samples=2),
               ds=f"d{i}", vcf=f"v{i}")
        for i in range(3)
    ]
    eng = _engine(
        *shards, use_mesh=True, l0_min_shards=3, response_cache=False
    )
    dist = DistributedEngine([], local=eng)
    try:
        assert dist.warmup() > 0
        for i in range(4):
            eng.add_delta(
                _shard([_rec("1", 800_000 + i)], ds="d0", vcf="v0")
            )
        assert eng.l0_status()["built"]
        pay = _bracket(chrom="1", datasets=["d0", "d1", "d2"])
        served0, mesh0 = eng.l0_searches, eng.mesh_searches
        launches0 = flight_recorder.launches_by_family().get("mesh", 0)
        ctx = RequestContext(route="test")
        with request_context(ctx):
            got = dist.search(pay)
        assert ctx.cost.delta_shards == 0
        assert eng.l0_searches > served0
        assert eng.mesh_searches == mesh0 + 1
        assert (
            flight_recorder.launches_by_family().get("mesh", 0)
            == launches0 + 1
        )
        assert any("800003" in v for v in _variants(got))
    finally:
        dist.close()
        eng.close()


def test_publish_burst_on_one_key_leaves_other_keys_l0_untouched():
    """ISSUE 20 regression: the L0 tier keeps per-(dataset, vcf)
    stacks — a deep publish burst on key A restacks ONLY key A's
    block. Key B's standing block (same object), its answers, and the
    compile tracker are untouched: zero mid-request compiles on
    either key after the burst."""
    import sbeacon_tpu.telemetry as tel

    recs_a = random_records(random.Random(70), chrom="1", n=400,
                            n_samples=2)
    recs_b = random_records(random.Random(71), chrom="1", n=400,
                            n_samples=2)
    eng = _engine(
        _shard(recs_a[:200]),
        _shard(recs_b[:200], ds="dsB", vcf="b.vcf"),
        l0_min_shards=3,
        response_cache=False,
    )
    try:
        for i in range(4):
            eng.add_delta(
                _shard(recs_a[200 + 40 * i:240 + 40 * i], vcf="a.vcf")
            )
            eng.add_delta(
                _shard(recs_b[200 + 40 * i:240 + 40 * i], ds="dsB",
                       vcf="b.vcf")
            )
        status = eng.l0_status()
        assert status["built"]
        assert set(status["keys"]) == {"dsA/a.vcf", "dsB/b.vcf"}
        a_builds = status["keys"]["dsA/a.vcf"]["builds"]
        b_builds = status["keys"]["dsB/b.vcf"]["builds"]
        b_block = eng._l0_blocks[("dsB", "b.vcf")][0]
        # warm both keys' serving paths, then snapshot the tracker
        pre_a = _variants(eng.search(_bracket(chrom="1",
                                              datasets=["dsA"])))
        pre_b = _variants(eng.search(_bracket(chrom="1",
                                              datasets=["dsB"])))
        assert pre_a and pre_b
        c0 = tel.flight_recorder.mid_request_compiles()
        # the burst: key A only
        for i in range(6):
            eng.add_delta(
                _shard([_rec("1", 500_000 + i)], vcf="a.vcf")
            )
        status = eng.l0_status()
        assert status["keys"]["dsA/a.vcf"]["builds"] > a_builds
        assert status["keys"]["dsB/b.vcf"]["builds"] == b_builds, (
            "a burst on key A restacked key B's L0 block"
        )
        assert eng._l0_blocks[("dsB", "b.vcf")][0] is b_block, (
            "key B's standing block was rebuilt, not reused"
        )
        assert status["blockReuses"] > 0
        # both keys still answer, and nothing compiled mid-request:
        # every composite shape the burst created was warmed at build
        got_a = _variants(eng.search(_bracket(chrom="1",
                                              datasets=["dsA"])))
        assert any("500005" in v for v in got_a)
        assert pre_a <= got_a
        assert _variants(eng.search(_bracket(chrom="1",
                                             datasets=["dsB"]))) == pre_b
        assert tel.flight_recorder.mid_request_compiles() - c0 == 0, (
            tel.flight_recorder.last_mid_request_compile()
        )
    finally:
        eng.close()


# -- size-tiered compaction + GC (ISSUE 15) -----------------------------------


def test_compactor_notify_folds_only_the_tripping_key(tmp_path):
    """Satellite regression: the depth trigger folds the (dataset,
    vcf) that tripped it — an unrelated key's deep tail is untouched
    by another key's trigger (inline path, background thread off)."""
    recs = random_records(random.Random(65), chrom="1", n=300,
                          n_samples=2)
    eng = _engine(
        _shard(recs[:100]),
        _shard(recs[100:200], ds="dsB", vcf="b.vcf"),
    )
    try:
        for i in range(3):
            eng.add_delta(_shard([_rec("1", 10_000 + i)], vcf="a.vcf"))
            eng.add_delta(_shard([_rec("1", 20_000 + i)], ds="dsB",
                                 vcf="b.vcf"))
        comp = _compactor(
            eng, tmp_path, delta_max_shards=2, compact_interval_s=0.0
        )
        comp.notify("dsA", "a.vcf", eng.delta_depth("dsA", "a.vcf"))
        # dsA folded — under the tiered DEFAULT (ISSUE 20) its tiny
        # tail consolidates into ONE standing L1 entry and the base
        # merge stays deferred (3 rows vs a 100-row base is far below
        # the byte ratio); dsB's equally deep raw tail MUST still
        # stand untouched
        stats = eng.delta_stats()
        assert stats["dsA"]["shards"] == 1
        assert stats["dsB"]["shards"] == 3, (
            "another key's trigger folded an unrelated tail"
        )
        assert comp.metrics()["tier_folds"] == {"l1": 1}
    finally:
        eng.close()


def test_tiered_fold_l1_then_base_on_byte_ratio(tmp_path):
    """The tier policy: raw tails fold into epoch-ranged L1 artifacts
    (base fingerprint untouched, write amplification ~1) and the full
    base merge only runs once accumulated L1 bytes reach the ratio —
    with per-fold tier/bytes/write-amp recorded in the ledger."""
    recs = random_records(random.Random(66), chrom="1", n=900,
                          n_samples=2)
    eng = _engine(_shard(recs[:500]), l0_min_shards=3)
    try:
        for i in range(4):
            eng.add_delta(
                _shard(recs[500 + 50 * i:550 + 50 * i], vcf="a.vcf")
            )
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        base_fp = eng.base_fingerprint()
        comp = _compactor(
            eng, tmp_path, compact_base_ratio=0.5, artifact_retain=1
        )
        folded = comp.run_once()
        assert folded[("dsA", "a.vcf")] > 0
        # L1 only: tail collapsed to one artifact entry, base untouched
        tail = eng.delta_stats()["dsA"]
        assert tail["shards"] == 1
        assert eng.base_fingerprint() == base_fp, (
            "an L1 fold must not re-merge or republish the base"
        )
        assert comp.metrics()["tier_folds"] == {"l1": 1}
        assert _variants(eng.search(q)) == pre
        # the artifact is persisted + epoch-ranged
        assert list(comp.pipeline.l1_dir("dsA", "a.vcf").glob("*.npz"))
        # accumulate more raws until the byte-ratio trigger fires
        for i in range(4):
            eng.add_delta(
                _shard(recs[700 + 50 * i:750 + 50 * i], vcf="a.vcf")
            )
        pre = _variants(eng.search(q))  # now includes the new rows
        folded = comp.run_once()
        assert folded[("dsA", "a.vcf")] > 0
        assert eng.delta_stats() == {}
        assert eng.base_fingerprint() != base_fp
        tiers = comp.metrics()["tier_folds"]
        assert tiers["l1"] == 2 and tiers["base"] == 1
        log = comp.pipeline.ledger.compaction_log()
        assert [e["tier"] for e in log] == ["l1", "l1", "base"]
        assert all(
            e["inBytes"] > 0 and e["outBytes"] > 0 and e["writeAmp"] > 0
            for e in log
        )
        # L1 write-amp ~1; the base fold's reflects rewriting the base
        assert log[0]["writeAmp"] < 1.5 < log[-1]["writeAmp"]
        assert _variants(eng.search(q)) == pre
    finally:
        eng.close()


@pytest.mark.resilience
def test_l1_crash_at_merge_seam_keeps_serving_then_refolds(tmp_path):
    recs = random_records(random.Random(67), chrom="1", n=400,
                          n_samples=2)
    eng = _engine(_shard(recs[:300]), l0_min_shards=3)
    try:
        for i in range(3):
            eng.add_delta(
                _shard(recs[300 + 33 * i:333 + 33 * i], vcf="a.vcf")
            )
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        pre_calls = sum(r.call_count for r in eng.search(q))
        comp = _compactor(eng, tmp_path, compact_base_ratio=10.0)
        faults.install(
            {
                "seed": 7,
                "rules": [
                    {
                        "site": "compaction.fold",
                        "kind": "error",
                        "rate": 1.0,
                        "count": 1,
                        "match": ":l1:merge",
                    }
                ],
            }
        )
        try:
            out = comp.run_once()
        finally:
            faults.uninstall()
        assert out == {}
        assert comp.metrics()["failures"] == 1
        # base + L0 + tail keep serving, duplicate-free
        assert eng.delta_stats()["dsA"]["shards"] == 3
        assert _variants(eng.search(q)) == pre
        assert sum(r.call_count for r in eng.search(q)) == pre_calls
        # next run re-folds
        folded = comp.run_once()
        assert folded[("dsA", "a.vcf")] > 0
        assert eng.delta_stats()["dsA"]["shards"] == 1
        assert _variants(eng.search(q)) == pre
    finally:
        eng.close()


@pytest.mark.resilience
def test_l1_crash_after_persist_adopts_artifact_on_retry(tmp_path):
    """Crash between the L1 save and the registry swap: the artifact
    is on disk, nothing served changed; the retry ADOPTS it (same
    inode — no re-merge) and completes the swap."""
    recs = random_records(random.Random(68), chrom="1", n=400,
                          n_samples=2)
    eng = _engine(_shard(recs[:300]), l0_min_shards=3)
    try:
        for i in range(3):
            eng.add_delta(
                _shard(recs[300 + 33 * i:333 + 33 * i], vcf="a.vcf")
            )
        q = _bracket(chrom="1")
        pre = _variants(eng.search(q))
        comp = _compactor(eng, tmp_path, compact_base_ratio=10.0)
        faults.install(
            {
                "seed": 7,
                "rules": [
                    {
                        "site": "compaction.fold",
                        "kind": "error",
                        "rate": 1.0,
                        "count": 1,
                        "match": ":l1:publish",
                    }
                ],
            }
        )
        try:
            out = comp.run_once()
        finally:
            faults.uninstall()
        assert out == {}
        arts = list(comp.pipeline.l1_dir("dsA", "a.vcf").glob("*.npz"))
        assert len(arts) == 1  # persisted, swap never happened
        stamp = arts[0].stat().st_mtime_ns
        assert eng.delta_stats()["dsA"]["shards"] == 3
        assert _variants(eng.search(q)) == pre
        folded = comp.run_once()
        assert folded[("dsA", "a.vcf")] > 0
        assert eng.delta_stats()["dsA"]["shards"] == 1
        # adopted, not re-merged: the artifact file was not rewritten
        assert arts[0].stat().st_mtime_ns == stamp
        assert _variants(eng.search(q)) == pre
    finally:
        eng.close()


def test_gc_reclaims_superseded_but_never_a_serving_artifact(tmp_path):
    """Retention GC only ever deletes from .retired/: after repeated
    base merges with retain=1, superseded generations are reclaimed
    (gc_bytes > 0) while the serving base artifact and the live
    answers survive every pass."""
    from sbeacon_tpu.index.columnar import load_index

    recs = random_records(random.Random(69), chrom="1", n=600,
                          n_samples=2)
    eng = _engine(_shard(recs[:300]))
    try:
        q = _bracket(chrom="1")
        comp = _compactor(
            eng, tmp_path, compact_base_ratio=0.01, artifact_retain=1
        )
        for round_ in range(3):
            lo = 300 + 100 * round_
            eng.add_delta(_shard(recs[lo:lo + 50], vcf="a.vcf"))
            eng.add_delta(_shard(recs[lo + 50:lo + 100], vcf="a.vcf"))
            folded = comp.run_once()  # tiny ratio: l1 then base merge
            assert folded[("dsA", "a.vcf")] > 0
            assert eng.delta_stats() == {}
            final = comp.pipeline.shard_path("dsA", "a.vcf")
            assert final.exists(), "GC deleted the serving artifact"
            load_index(final)  # and it is intact
            got = eng.search(q)
            assert any(r.exists for r in got)
        m = comp.metrics()
        assert m["tier_folds"]["base"] == 3
        assert m["gc_bytes"] > 0, "retention GC never reclaimed"
        # retain=1 keeps ONE generation (a merge's base + its L1s as
        # one rollback unit), not one file
        retired = comp.pipeline.retired_dir("dsA", "a.vcf")
        gens = {
            p.name.split("-", 1)[0] for p in retired.glob("*.npz")
        }
        assert len(gens) <= 1
        # final answers cover every folded round's rows
        mono = _engine(_shard(recs[:600]))
        try:
            assert _variants(eng.search(q)) == _variants(
                mono.search(q)
            )
        finally:
            mono.close()
    finally:
        eng.close()
