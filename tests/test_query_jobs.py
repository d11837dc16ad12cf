"""Async query job table: state machine, TTL, spill, coalescing, caching.

Covers the re-homed VariantQueries / VariantQueryResponses semantics
(reference: shared_resources/dynamodb/variant_queries.py and
performQuery/search_variants.py:282-315) — implemented for real where the
reference stubs get_job_status to always-NEW.
"""

import threading
import time

from sbeacon_tpu.payloads import VariantQueryPayload, VariantSearchResponse
from sbeacon_tpu.query_jobs import (
    AsyncQueryRunner,
    JobStatus,
    QueryJobTable,
    hash_query,
)


def make_resp(ds="ds1", n_variants=1):
    return VariantSearchResponse(
        dataset_id=ds,
        vcf_location="v.vcf.gz",
        exists=True,
        call_count=10,
        all_alleles_count=20,
        variants=[f"22\t{100 + i}\tA\tT\tSNP" for i in range(n_variants)],
    )


def test_hash_query_stable_and_order_insensitive():
    a = hash_query({"x": 1, "y": [2, 3]})
    b = hash_query({"y": [2, 3], "x": 1})
    assert a == b
    assert hash_query({"x": 2}) != a


def test_job_lifecycle_and_counters():
    t = QueryJobTable()
    qid = "q1"
    assert t.get_job_status(qid) is JobStatus.NEW
    claim = t.start(qid, fan_out=2)
    assert claim is not None
    assert t.start(qid) is None  # second claim rejected
    assert t.get_job_status(qid) is JobStatus.RUNNING
    assert t.next_response_number(qid, claim) == 1
    assert t.next_response_number(qid, claim) == 2
    assert t.put_response(qid, 1, make_resp(), claim)
    assert t.mark_finished(qid, claim) == 1
    assert t.put_response(qid, 2, make_resp(n_variants=2), claim)
    assert t.mark_finished(qid, claim) == 0
    assert t.complete(qid, claim)
    assert t.get_job_status(qid) is JobStatus.COMPLETED
    resps = t.get_responses(qid)
    assert [len(r.variants) for r in resps] == [1, 2]
    info = t.info(qid)
    assert info["responses"] == 2 and info["fan_out"] == 0
    assert info["elapsed_time"] >= 0


def test_ttl_expiry_and_restart(tmp_path):
    t = QueryJobTable(query_ttl_s=0.05)
    c1 = t.start("q")
    assert c1
    time.sleep(0.06)
    assert t.get_job_status("q") is JobStatus.EXPIRED
    # an expired claim can be re-taken, and the stale responses are purged
    t.put_response("q", 1, make_resp(), c1)
    c2 = t.start("q")
    assert c2 and c2 != c1
    assert t.get_responses("q") == []


def test_lost_claim_cannot_write():
    """The double-write hazard: a worker whose TTL-expired job was
    reclaimed by a new identical request must not corrupt the new job."""
    t = QueryJobTable(query_ttl_s=0.05)
    old = t.start("q")
    time.sleep(0.06)
    new = t.start("q")  # reclaim after expiry
    assert new is not None
    # old worker finishes late: every write is refused
    assert t.next_response_number("q", old) == 0
    assert not t.put_response("q", 1, make_resp(), old)
    assert t.mark_finished("q", old) == -1
    assert not t.complete("q", old)
    assert t.get_job_status("q") is JobStatus.RUNNING  # still the new job
    t.abandon("q", old)  # refused too
    assert t.get_job_status("q") is JobStatus.RUNNING
    assert t.get_responses("q") == []


def test_crash_recovery_clears_incomplete(tmp_path):
    """Rows with complete=0 from a dead process are dropped at open so
    identical queries don't stall on a claim nobody holds."""
    db = tmp_path / "jobs.sqlite"
    t1 = QueryJobTable(db, spill_dir=tmp_path / "s", inline_limit=8)
    c = t1.start("crashed")
    t1.put_response("crashed", 1, make_resp(n_variants=20), c)
    cd = t1.start("completed")
    t1.complete("completed", cd)
    spills = list((tmp_path / "s").glob("*.json"))
    assert spills
    t1.close()
    t2 = QueryJobTable(db, spill_dir=tmp_path / "s")
    assert t2.get_job_status("crashed") is JobStatus.NEW
    assert t2.get_job_status("completed") is JobStatus.COMPLETED
    assert not list((tmp_path / "s").glob("*.json"))  # spill unlinked


def test_reclaim_unlinks_spill(tmp_path):
    t = QueryJobTable(
        spill_dir=tmp_path / "s", inline_limit=8, query_ttl_s=0.05
    )
    c = t.start("q")
    t.put_response("q", 1, make_resp(n_variants=20), c)
    assert list((tmp_path / "s").glob("*.json"))
    time.sleep(0.06)
    assert t.start("q")  # reclaim purges row AND spill file
    assert not list((tmp_path / "s").glob("*.json"))


def test_spill_roundtrip(tmp_path):
    t = QueryJobTable(spill_dir=tmp_path / "spill", inline_limit=64)
    c = t.start("q")
    big = make_resp(n_variants=50)  # serializes well past 64 bytes
    assert t.put_response("q", 1, big, c)
    spills = list((tmp_path / "spill").glob("*.json"))
    assert len(spills) == 1
    (got,) = t.get_responses("q")
    assert got.variants == big.variants


def test_purge_expired_removes_spill(tmp_path):
    t = QueryJobTable(
        spill_dir=tmp_path / "s",
        inline_limit=8,
        query_ttl_s=0.01,
        response_ttl_s=0.01,
    )
    c = t.start("q")
    t.put_response("q", 1, make_resp(n_variants=20), c)
    assert list((tmp_path / "s").glob("*.json"))
    time.sleep(0.03)
    assert t.purge_expired() >= 2
    assert not list((tmp_path / "s").glob("*.json"))
    assert t.get_job_status("q") is JobStatus.NEW


def test_wait_polls_to_completion():
    t = QueryJobTable()
    c = t.start("q")

    def finish():
        time.sleep(0.03)
        t.complete("q", c)

    th = threading.Thread(target=finish)
    th.start()
    assert t.wait("q", timeout_s=5)
    th.join()
    assert not t.wait("nonexistent", timeout_s=0.01)


class SlowEngine:
    """Counts searches; optional delay to hold jobs in RUNNING."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.calls = 0
        self._lock = threading.Lock()

    def search(self, payload):
        with self._lock:
            self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        return [make_resp()]


def test_runner_executes_and_caches():
    eng = SlowEngine()
    table = QueryJobTable()
    runner = AsyncQueryRunner(eng, table)
    pl = VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    qid, _ = runner.submit(pl)
    resps = runner.result(qid, wait_s=5)
    assert resps and resps[0].exists
    assert eng.calls == 1
    # identical resubmit: served from cache, no new search
    qid2, status = runner.submit(pl)
    assert qid2 == qid and status is JobStatus.COMPLETED
    assert runner.result(qid2) is not None
    assert eng.calls == 1


def test_runner_sweep_drops_expired_handoffs():
    """The purge thread runs to its end over the (responses, expiry,
    unavailable) handoffs and drops the expired ones."""
    runner = AsyncQueryRunner(SlowEngine(), QueryJobTable(query_ttl_s=0.01))
    qid, _ = runner.submit(
        VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    )
    assert runner.result(qid, wait_s=5)
    time.sleep(0.05)
    runner._last_purge = 0.0
    runner._maybe_purge()
    runner._sweeper.join(timeout=5)
    assert qid not in runner._results


def test_runner_fingerprint_invalidates():
    eng = SlowEngine()
    table = QueryJobTable()
    runner = AsyncQueryRunner(eng, table)
    pl = VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    qid1, _ = runner.submit(pl, fingerprint="v1")
    runner.result(qid1, wait_s=5)
    qid2, _ = runner.submit(pl, fingerprint="v2")
    assert qid2 != qid1
    runner.result(qid2, wait_s=5)
    assert eng.calls == 2


def test_runner_coalesces_concurrent_identical():
    eng = SlowEngine(delay=0.1)
    table = QueryJobTable()
    runner = AsyncQueryRunner(eng, table)
    pl = VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    results = []

    def go():
        qid, _ = runner.submit(pl)
        results.append(runner.result(qid, wait_s=5))

    threads = [threading.Thread(target=go) for _ in range(5)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert eng.calls == 1  # one execution served all five
    assert all(r for r in results)


def test_runner_failure_still_completes():
    class BoomEngine:
        def search(self, payload):
            raise RuntimeError("boom")

    table = QueryJobTable()
    runner = AsyncQueryRunner(BoomEngine(), table)
    pl = VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    qid, _ = runner.submit(pl)
    # the failed job is abandoned (never cached as an empty result):
    # result() returns None and the id reads NEW again for a retry
    assert runner.result(qid, wait_s=5) is None
    deadline = time.time() + 5
    while table.get_job_status(qid) is not JobStatus.NEW:
        assert time.time() < deadline
        time.sleep(0.005)
