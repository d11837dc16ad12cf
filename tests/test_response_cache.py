"""Response-cache behavior: invalidation on ingest, negative entries,
TTL/size bounds, and copy-isolation of served responses."""

import random
import time

import pytest

import sbeacon_tpu.ops.kernel as kernel_mod
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.response_cache import ResponseCache, response_cache_key
from sbeacon_tpu.testing import random_records


def _shard(seed: int, dataset_id: str):
    rng = random.Random(seed)
    recs = random_records(rng, chrom="1", n=200, n_samples=2)
    return build_index(
        recs,
        dataset_id=dataset_id,
        vcf_location=f"{dataset_id}.vcf",
        sample_names=["S0", "S1"],
    )


def _engine(*shards, **eng_over) -> VariantEngine:
    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(use_mesh=False, **eng_over))
    )
    for s in shards:
        eng.add_index(s)
    return eng


def _bracket_payload(**over) -> VariantQueryPayload:
    kw = dict(
        dataset_ids=[],
        reference_name="1",
        start_min=1,
        start_max=1 << 29,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity="count",
        include_datasets="HIT",
    )
    kw.update(over)
    return VariantQueryPayload(**kw)


def test_ingest_invalidates_cached_query():
    """add_index bumps index_fingerprint(): a previously cached query
    must re-execute and now include the new dataset."""
    eng = _engine(_shard(1, "dsA"))
    try:
        pay = _bracket_payload()
        first = eng.search(pay)
        assert [r.dataset_id for r in first] == ["dsA"]
        cached = eng.search(pay)  # warm
        assert eng.cache_stats()["hits"] == 1
        assert [r.dataset_id for r in cached] == ["dsA"]

        fp_before = eng.index_fingerprint()
        eng.add_index(_shard(2, "dsB"))
        assert eng.index_fingerprint() != fp_before
        # the publish cleared the cache AND the fingerprint changed the
        # key — either alone forces re-execution
        assert eng.cache_stats()["invalidations"] >= 1

        after = eng.search(pay)
        assert sorted(r.dataset_id for r in after) == ["dsA", "dsB"]
    finally:
        eng.close()


def test_negative_result_cached_and_served_without_dispatch():
    """A query matching NOTHING caches its miss: the repeat answers
    without any device launch (the dominant Beacon workload)."""
    eng = _engine(_shard(3, "dsA"))
    try:
        # position range beyond every record: exists=False everywhere
        pay = _bracket_payload(
            start_min=(1 << 28), start_max=(1 << 28) + 10
        )
        miss = eng.search(pay)
        assert not any(r.exists for r in miss)
        n0 = kernel_mod.N_LAUNCHES
        again = eng.search(pay)
        assert kernel_mod.N_LAUNCHES == n0  # zero launches on the repeat
        assert not any(r.exists for r in again)
        stats = eng.cache_stats()
        assert stats["hits"] == 1 and stats["negative_hits"] == 1
    finally:
        eng.close()


def test_served_responses_are_copy_isolated():
    """Mutating a served response must not corrupt the cached entry."""
    eng = _engine(_shard(4, "dsA"))
    try:
        pay = _bracket_payload()
        first = eng.search(pay)
        first[0].variants.append("CORRUPTED")
        first[0].sample_names.append("EVE")
        again = eng.search(pay)
        assert "CORRUPTED" not in again[0].variants
        assert "EVE" not in again[0].sample_names
    finally:
        eng.close()


def test_key_normalization_and_shaping_fields():
    """Case-insensitive alleles and unordered dataset ids share an
    entry; response-shaping fields (granularity) split entries."""
    fp = "fp1"
    a = response_cache_key(fp, _bracket_payload(alternate_bases="acGT"))
    b = response_cache_key(fp, _bracket_payload(alternate_bases="ACGT"))
    assert a == b
    c = response_cache_key(
        fp, _bracket_payload(dataset_ids=["d2", "d1"])
    )
    d = response_cache_key(
        fp, _bracket_payload(dataset_ids=["d1", "d2"])
    )
    assert c == d
    e = response_cache_key(
        fp, _bracket_payload(requested_granularity="boolean")
    )
    assert e != a
    assert response_cache_key("fp2", _bracket_payload()) != (
        response_cache_key(fp, _bracket_payload())
    )


def test_lru_eviction_and_ttl():
    cache = ResponseCache(max_entries=2, ttl_s=0.05)
    cache.put(("k1",), [])
    cache.put(("k2",), [])
    cache.put(("k3",), [])  # evicts k1
    assert cache.get(("k1",)) is None
    assert cache.get(("k2",)) is not None
    assert cache.stats()["evictions"] == 1
    time.sleep(0.06)
    assert cache.get(("k2",)) is None  # expired
    assert cache.stats()["expirations"] == 1


def test_cache_disabled_by_config():
    eng = _engine(_shard(5, "dsA"), response_cache=False)
    try:
        assert eng.cache_stats() is None
        pay = _bracket_payload()
        # the counter is the process's: an earlier test's unclosed app
        # may run its canary round meanwhile, so the least of a few
        launched = []
        for _ in range(3):
            n0 = kernel_mod.N_LAUNCHES
            eng.search(pay)
            eng.search(pay)
            launched.append(kernel_mod.N_LAUNCHES - n0)
            if launched[-1] == 2:
                break
        assert min(launched) == 2, launched  # both executed
    finally:
        eng.close()


def test_ttl_zero_means_no_expiry():
    cache = ResponseCache(max_entries=8, ttl_s=0)
    cache.put(("k",), [])
    time.sleep(0.02)
    assert cache.get(("k",)) is not None
