"""Async query job table: state machine, TTL, spill, coalescing, caching.

Covers the re-homed VariantQueries / VariantQueryResponses semantics
(reference: shared_resources/dynamodb/variant_queries.py and
performQuery/search_variants.py:282-315) — implemented for real where the
reference stubs get_job_status to always-NEW.
"""

import sys
import threading
import time

import pytest

from sbeacon_tpu import query_jobs
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


def _until(cond, timeout_s=10.0):
    deadline = time.time() + timeout_s
    while not cond():
        assert time.time() < deadline, "timed out"
        time.sleep(0.002)


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
    """The writer's sweep runs to its end over the (responses, expiry,
    unavailable, clock) handoffs and drops the expired ones."""
    runner = AsyncQueryRunner(SlowEngine(), QueryJobTable(query_ttl_s=0.01))
    qid, _ = runner.submit(
        VariantQueryPayload(dataset_ids=["ds1"], reference_name="22")
    )
    assert runner.result(qid, wait_s=5)
    time.sleep(0.05)
    runner._next_sweep = 0.0  # interval lapsed; the next job's row wakes it
    runner.submit(VariantQueryPayload(dataset_ids=["ds1"], reference_name="21"))
    _until(lambda: qid not in runner._results)
    runner.close()


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
    _until(lambda: runner.poll(qid) is JobStatus.NEW)
    assert table.get_job_status(qid) is JobStatus.NEW


# -- the table off the request path: a write-behind journal, one writer ---------


class StubEngine:
    """``n`` responses a search, big enough to spill past a small
    ``inline_limit``; a payload on contig "hold" blocks until released."""

    def __init__(self, n=1, n_variants=1, delay=0.0):
        self.n, self.n_variants, self.delay = n, n_variants, delay
        self.calls = 0
        self.hold = threading.Event()
        self._lock = threading.Lock()

    def search(self, payload):
        with self._lock:
            self.calls += 1
        if payload.reference_name == "hold":
            assert self.hold.wait(20), "test deadlock"
        if self.delay:
            time.sleep(self.delay)
        return [
            make_resp(ds=f"ds{i}", n_variants=self.n_variants)
            for i in range(self.n)
        ]


def _pl(k, contig="22"):
    return VariantQueryPayload(
        dataset_ids=["ds"], reference_name=contig, start_min=k, start_max=k
    )


def _statements(table) -> list:
    """Every statement the table's connection runs from here on, with
    the thread that ran it."""
    seen = []
    table._conn.set_trace_callback(
        lambda sql: seen.append((threading.current_thread(), sql))
    )
    return seen


def _rows(table) -> tuple:
    return tuple(
        table._conn.execute(f"SELECT count(*) FROM {t}").fetchone()[0]
        for t in ("variant_queries", "variant_query_responses")
    )


class _Seam:
    """Stands in for the ``sqlite.commit`` fault seam: parks the first
    batch that reaches it until released, and notes what the runner's
    counters read as each batch arrives."""

    def __init__(self, runner, monkeypatch):
        self.runner = runner
        self.parked = threading.Event()
        self.release = threading.Event()
        self.read = []
        monkeypatch.setattr(query_jobs, "fault_point", self)

    def __call__(self, site, detail=""):
        assert site == "sqlite.commit"
        assert threading.current_thread() is self.runner._writer
        self.read.append(
            (self.runner._n_persisted_jobs, self.runner._n_persist_commits)
        )
        self.parked.set()
        assert self.release.wait(20), "test deadlock"


@pytest.mark.parametrize("n", [1, 4, 32])
def test_a_miss_costs_no_statement_and_a_job_is_one_transaction(n):
    table = QueryJobTable()
    runner = AsyncQueryRunner(StubEngine(n), table)
    seen = _statements(table)
    try:
        qid, status = runner.submit(_pl(1))
        assert status is JobStatus.RUNNING
        assert len(runner.result(qid, wait_s=5)) == n
        assert runner.poll(qid) is JobStatus.COMPLETED
        _until(lambda: runner._n_persisted_jobs == 1)
        # nothing on the calling thread, nothing on the pool's
        assert {t for t, _sql in seen} == {runner._writer}
        # as many statements for 32 responses as for one
        assert [s.split()[0] for _t, s in seen] == [
            "BEGIN", "DELETE", "INSERT", "INSERT", "COMMIT",
        ]
        assert (runner._n_persisted_jobs, runner._n_persist_commits) == (1, 1)
    finally:
        table._conn.set_trace_callback(None)
        runner.close()
    info = table.info(qid)
    assert info["complete"] == 1 and info["fan_out"] == 0
    assert info["responses"] == n and info["responses_counter"] == n
    assert info["elapsed_time"] >= 0
    assert info["start_time"] <= info["end_time"] < info["expires_at"]
    assert table.get_job_status(qid) is JobStatus.COMPLETED
    assert [r.dataset_id for r in table.get_responses(qid)] == [
        f"ds{i}" for i in range(n)
    ]
    table.close()


def test_a_restart_keeps_what_the_writer_wrote(tmp_path, monkeypatch):
    """Completed jobs answer as ``table_hit`` from a reopened table,
    spilled bodies included; ``close()`` drains what was queued; a job
    in flight at the "crash" left no row and reads NEW."""
    db, spill = tmp_path / "jobs.sqlite", tmp_path / "s"
    t1 = QueryJobTable(db, spill_dir=spill, inline_limit=64)
    eng = StubEngine(2, n_variants=50)
    r1 = AsyncQueryRunner(eng, t1)
    seam = _Seam(r1, monkeypatch)
    qids = []
    for k in range(3):
        qid, _ = r1.submit(_pl(k), fingerprint="fp")
        assert len(r1.result(qid, wait_s=5)) == 2
        qids.append(qid)
        assert seam.parked.wait(5)  # the first job is at the seam ...
    _until(lambda: r1._queue.qsize() == 2)  # ... the others queued
    in_flight, _ = r1.submit(_pl(9, contig="hold"), fingerprint="fp")
    closer = threading.Thread(target=r1.close)
    closer.start()
    closer.join(0.05)
    assert closer.is_alive()  # close() waits for the writer
    seam.release.set()
    closer.join(10)
    assert not closer.is_alive()
    assert r1._n_persisted_jobs == 3 and r1._queue.qsize() == 0
    assert len(list(spill.glob("*.json"))) == 6
    t1.close()
    monkeypatch.undo()

    t2 = QueryJobTable(db, spill_dir=spill, inline_limit=64)
    assert set(t2.restored) == set(qids)
    assert t2.get_job_status(in_flight) is JobStatus.NEW
    eng2 = StubEngine()
    r2 = AsyncQueryRunner(eng2, t2)
    try:
        seen = _statements(t2)
        for k, qid in enumerate(qids):
            assert r2.submit(_pl(k), fingerprint="fp") == (
                qid, JobStatus.COMPLETED,
            )
            assert seen == []  # the lookup itself is memory
            got = r2.result(qid, wait_s=5)
            assert [len(r.variants) for r in got] == [50, 50]
            assert [r.dataset_id for r in got] == ["ds0", "ds1"]
            assert len(seen) == 1 and seen.pop()[1].startswith("SELECT")
        assert r2._n_table_hits == 3 and eng2.calls == 0
        assert r2.poll(qids[0]) is JobStatus.COMPLETED
        assert r2.poll(in_flight) is JobStatus.NEW
        # the index fingerprint is in the id: other data, other job
        other, status = r2.submit(_pl(0), fingerprint="fp2")
        assert other not in qids and status is JobStatus.RUNNING
        assert r2.result(other, wait_s=5) and eng2.calls == 1
    finally:
        t2._conn.set_trace_callback(None)
        eng.hold.set()
        r2.close()
        t2.close()


def test_queued_jobs_share_one_commit(monkeypatch):
    """Group commit: what waits while the writer is busy (here: parked
    at the ``sqlite.commit`` seam) lands in ONE transaction."""
    k, n = 7, 32  # more rows than one statement binds
    table = QueryJobTable()
    assert k * n * 5 > table.MAX_BOUND
    runner = AsyncQueryRunner(StubEngine(n), table)
    seam = _Seam(runner, monkeypatch)
    try:
        qid, _ = runner.submit(_pl(0))
        assert runner.result(qid, wait_s=5)
        assert seam.parked.wait(5)
        for i in range(1, k + 1):
            qid, _ = runner.submit(_pl(i))
            assert runner.result(qid, wait_s=5)  # answered, not yet stored
        _until(lambda: runner._queue.qsize() == k)
        assert _rows(table) == (0, 0)
        seen = _statements(table)
        seam.release.set()
        _until(lambda: runner._n_persisted_jobs == 1 + k)
        # the seam fired once a transaction; between its two readings
        # lies the parked job's commit, after the second the k jobs'
        assert seam.read == [(0, 0), (1, 1)]
        assert (runner._n_persisted_jobs, runner._n_persist_commits) == (
            1 + k, 2,
        )
        assert sum(s == "COMMIT" for _t, s in seen) == 2
        assert _rows(table) == (1 + k, n * (1 + k))
        assert [r.dataset_id for r in table.get_responses(qid)] == [
            f"ds{i}" for i in range(n)
        ]
    finally:
        table._conn.set_trace_callback(None)
        seam.release.set()
        runner.close()
        table.close()


@pytest.mark.parametrize("fate", ["partial", "failing"])
def test_a_degraded_or_failed_job_is_never_stored(tmp_path, fate):
    from sbeacon_tpu.telemetry import (
        RequestContext,
        annotate,
        request_context,
    )

    class Engine(StubEngine):
        def search(self, payload):
            if fate == "failing":
                raise RuntimeError("boom")
            annotate(unavailable_datasets=("rz",))
            return super().search(payload)

    table = QueryJobTable(spill_dir=tmp_path / "s", inline_limit=8)
    runner = AsyncQueryRunner(Engine(2, n_variants=20), table)
    with request_context(RequestContext(route="a")):
        qid, _ = runner.submit(_pl(1))
        got = runner.result(qid, wait_s=5)
    assert (got is None) if fate == "failing" else (len(got) == 2)
    _until(lambda: runner.metrics()["active"] == 0)
    assert runner.poll(qid) is JobStatus.NEW
    runner.close()  # whatever was queued is written by now
    assert _rows(table) == (0, 0)
    assert runner._n_persisted_jobs == 0 and runner._n_persist_expired == 0
    assert not list((tmp_path / "s").glob("*.json"))
    table.close()


def test_no_request_waits_for_the_sweep(monkeypatch):
    """Purge and checkpoint run on the writer thread between batches:
    while it is parked in a checkpoint, a new query is answered in the
    stub's search time."""
    table = QueryJobTable()
    runner = AsyncQueryRunner(StubEngine(delay=0.02), table)
    parked, leave = threading.Event(), threading.Event()
    checkpoint = table.checkpoint

    def slow_checkpoint():
        assert threading.current_thread() is runner._writer
        parked.set()
        assert leave.wait(20), "test deadlock"
        checkpoint()

    monkeypatch.setattr(table, "checkpoint", slow_checkpoint)
    try:
        runner._next_sweep = 0.0  # due: the next batch is followed by it
        qid, _ = runner.submit(_pl(0))
        assert runner.result(qid, wait_s=5)
        assert parked.wait(5)
        t0 = time.perf_counter()
        qid, status = runner.submit(_pl(1))
        assert status is JobStatus.RUNNING
        assert runner.result(qid, wait_s=5)
        assert runner.submit(_pl(1)) == (qid, JobStatus.COMPLETED)
        assert time.perf_counter() - t0 < 2.0
        assert runner._n_persisted_jobs == 1  # its row waits; nobody else
        leave.set()
        _until(lambda: runner._n_persisted_jobs == 2)
    finally:
        leave.set()
        runner.close()
        table.close()


def test_the_writer_skips_a_job_that_expired_in_its_queue(monkeypatch):
    table = QueryJobTable(query_ttl_s=0.05)
    runner = AsyncQueryRunner(StubEngine(), table)
    seam = _Seam(runner, monkeypatch)
    try:
        for k in range(3):
            qid, _ = runner.submit(_pl(k))
            assert runner.result(qid, wait_s=5)
            assert seam.parked.wait(5)  # the first alone, two behind it
        _until(lambda: runner._queue.qsize() == 2)
        time.sleep(0.08)  # the two that wait outlive their hand-off
        seam.release.set()
        _until(lambda: runner._n_persist_expired == 2)
        assert (runner._n_persisted_jobs, runner._n_persist_commits) == (1, 1)
        assert _rows(table) == (1, 1)
    finally:
        seam.release.set()
        runner.close()
        table.close()


def test_single_flight_is_exact_under_contention():
    """More submitters than cores over a few distinct queries, the
    interpreter switching every 10 us: each query runs once, every
    caller gets its answer, and each job is stored once."""
    distinct, callers = 6, 48
    eng = StubEngine(delay=0.01)
    table = QueryJobTable()
    runner = AsyncQueryRunner(eng, table, max_pending=distinct)
    start = threading.Barrier(callers)
    answers = []

    def go(i):
        start.wait(10)
        qid, _ = runner.submit(_pl(i % distinct))
        answers.append((i % distinct, qid, runner.result(qid, wait_s=10)))

    threads = [threading.Thread(target=go, args=(i,)) for i in range(callers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    runner.close()
    assert eng.calls == distinct
    assert len(answers) == callers and all(got for _k, _q, got in answers)
    assert len({(k, q) for k, q, _got in answers}) == distinct
    assert runner._n_submits == callers
    assert runner._n_memory_hits + runner._coalesced == callers - distinct
    assert runner._n_persisted_jobs == distinct
    assert _rows(table) == (distinct, distinct)
    assert runner.metrics()["active"] == 0 and not runner._done
    table.close()


def test_the_seam_fails_a_commit_and_the_writer_goes_on(tmp_path):
    """The real ``sqlite.commit`` fault seam, matched to the writer's
    transaction: an injected error stores nothing of that batch (its
    spills removed), the requests were answered regardless, and the
    next batch is stored."""
    from sbeacon_tpu.harness import faults

    table = QueryJobTable(spill_dir=tmp_path / "s", inline_limit=8)
    runner = AsyncQueryRunner(StubEngine(2, n_variants=20), table)
    inj = faults.install(
        {"rules": [{"site": "sqlite.commit", "kind": "error",
                    "match": "write_jobs", "count": 1}]}
    )
    try:
        lost, _ = runner.submit(_pl(0))
        assert len(runner.result(lost, wait_s=5)) == 2
        _until(lambda: inj.stats()["sqlite.commit[0]:write_jobs"]
               ["activations"] == 1)
        kept, _ = runner.submit(_pl(1))
        assert len(runner.result(kept, wait_s=5)) == 2
        _until(lambda: runner._n_persisted_jobs == 1)
        assert runner.submit(_pl(0)) == (lost, JobStatus.COMPLETED)  # memory
    finally:
        faults.uninstall()
        runner.close()
    assert table.get_job_status(lost) is JobStatus.NEW
    assert table.get_job_status(kept) is JobStatus.COMPLETED
    assert _rows(table) == (1, 2)
    assert len(list((tmp_path / "s").glob("*.json"))) == 2
    table.close()


def test_rewriting_an_id_replaces_its_rows_and_spills(tmp_path):
    """A query that runs again after its TTL finds the response rows of
    its last run (they live 24 h): replaced, their spills unlinked."""
    table = QueryJobTable(spill_dir=tmp_path / "s", inline_limit=8)
    now = time.time()
    for n in (3, 1):
        table.write_jobs([
            query_jobs.FinishedJob(
                "q", [make_resp(n_variants=20)] * n, now, now, now + 300
            )
        ])
        assert _rows(table) == (1, n)
        assert len(list((tmp_path / "s").glob("*.json"))) == n
    assert len(table.get_responses("q")) == 1
    table.close()
