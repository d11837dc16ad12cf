"""Resilience layer: deadlines, load shedding, circuit breaking, and
seeded fault injection (resilience.py + harness/faults.py).

Fast failure-path tests carry ``@pytest.mark.resilience`` (the tier-1
safe ``pytest -m resilience`` alias); the chaos soak is ``slow``.
"""

import random
import threading
import time

import pytest

from sbeacon_tpu.harness import faults
from sbeacon_tpu.resilience import (
    NO_DEADLINE,
    AdmissionController,
    BatchTimeout,
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    DeadlineExceeded,
    Overloaded,
    ResilienceError,
    current_deadline,
    deadline_scope,
)

resilience = pytest.mark.resilience


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    faults.uninstall()


# -- deadlines ----------------------------------------------------------------


@resilience
def test_deadline_basics():
    assert NO_DEADLINE.remaining() is None
    assert not NO_DEADLINE.expired()
    assert NO_DEADLINE.clamp(5.0) == 5.0
    assert NO_DEADLINE.clamp(None) is None
    assert Deadline.after(None) is NO_DEADLINE
    assert Deadline.after(0) is NO_DEADLINE

    d = Deadline.after(10.0)
    assert 9.0 < d.remaining() <= 10.0
    assert not d.expired()
    assert d.clamp(5.0) == 5.0
    assert d.clamp(None) <= 10.0
    # combine takes the tighter bound in both directions
    assert d.combine(2.0).remaining() <= 2.0
    assert d.combine(100.0).remaining() <= 10.0

    expired = Deadline.after(0.001)
    time.sleep(0.01)
    assert expired.expired()
    assert expired.remaining() == 0.0
    with pytest.raises(DeadlineExceeded):
        expired.check("unit test")


@resilience
def test_deadline_scope_is_thread_local():
    d = Deadline.after(30.0)
    assert current_deadline() is NO_DEADLINE
    with deadline_scope(d):
        assert current_deadline() is d
        seen = []

        def other():
            seen.append(current_deadline())

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen == [NO_DEADLINE]  # scopes do not leak across threads
    assert current_deadline() is NO_DEADLINE


# -- admission control --------------------------------------------------------


@resilience
def test_admission_sheds_past_cap_and_recovers():
    adm = AdmissionController(2, retry_after_s=3.0)
    with adm.admit():
        with adm.admit():
            with pytest.raises(Overloaded) as ei:
                with adm.admit():
                    pass
            assert ei.value.status == 429
            assert ei.value.retry_after_s == 3.0
            assert adm.metrics()["in_flight"] == 2
    m = adm.metrics()
    assert m["in_flight"] == 0
    assert m["admitted"] == 2
    assert m["shed"] == 1
    with adm.admit():  # capacity is back
        assert adm.metrics()["in_flight"] == 1


# -- circuit breaker ----------------------------------------------------------


@resilience
def test_circuit_breaker_transitions():
    clock = [0.0]
    br = CircuitBreaker(
        failure_threshold=3,
        reset_timeout_s=10.0,
        half_open_probes=1,
        clock=lambda: clock[0],
    )
    url = "http://w1"
    for _ in range(2):
        assert br.allow(url)
        br.record_failure(url)
    assert br.state(url) == "closed"
    assert br.allow(url)
    br.record_failure(url)  # third consecutive failure opens
    assert br.state(url) == "open"
    assert not br.allow(url)
    assert br.metrics()[url]["opens"] == 1

    clock[0] = 10.0  # reset window lapsed: one half-open probe
    assert br.state(url) == "half_open"
    assert br.allow(url)
    assert not br.allow(url)  # probes are consumed
    br.record_failure(url)  # failed probe re-opens with a fresh window
    assert br.state(url) == "open"
    assert not br.allow(url)
    assert br.metrics()[url]["opens"] == 2

    clock[0] = 20.0
    assert br.allow(url)
    br.record_success(url)  # successful probe closes
    assert br.state(url) == "closed"
    assert br.allow(url)
    # success also reset the consecutive-failure count
    assert br.metrics()[url]["consecutive_failures"] == 0


@resilience
def test_circuit_breaker_half_open_is_not_terminal():
    """A consumed probe whose holder never reports an outcome (died,
    deadline expired before the attempt) must not wedge HALF_OPEN
    forever: another reset window replenishes the probe."""
    clock = [0.0]
    br = CircuitBreaker(
        failure_threshold=1,
        reset_timeout_s=5.0,
        half_open_probes=1,
        clock=lambda: clock[0],
    )
    br.record_failure("w")  # open
    clock[0] = 5.0
    assert br.allow("w")  # half-open probe consumed...
    assert not br.allow("w")  # ...and nothing reported back
    clock[0] = 9.0
    assert not br.allow("w")  # within the window: still gated
    clock[0] = 10.0
    assert br.allow("w")  # window lapsed again: fresh probe
    br.record_success("w")
    assert br.state("w") == "closed"


# -- micro-batcher ------------------------------------------------------------


@pytest.fixture(scope="module")
def dindex():
    from sbeacon_tpu.index.columnar import build_index
    from sbeacon_tpu.ops.kernel import DeviceIndex
    from sbeacon_tpu.testing import random_records

    rng = random.Random(11)
    recs = random_records(rng, chrom="1", n=120, n_samples=2)
    shard = build_index(
        recs, dataset_id="ds", vcf_location="v", sample_names=["S0", "S1"]
    )
    return shard, DeviceIndex(shard, pad_unit=1024)


def _spec(shard):
    from sbeacon_tpu.ops.kernel import QuerySpec

    p = int(shard.cols["pos"][0])
    return QuerySpec(
        "1", max(1, p - 5), p + 5, 1, 1 << 30, alternate_bases="N"
    )


def _wedge_launches(monkeypatch):
    """Patch the serving-module kernel dispatch to block until released;
    returns (in_execute, release) events."""
    import sbeacon_tpu.serving as serving_mod

    release = threading.Event()
    in_execute = threading.Event()
    orig = serving_mod.run_queries_auto

    def wedged(index, queries, **kw):
        in_execute.set()
        assert release.wait(15), "test deadlock"
        return orig(index, queries, **kw)

    monkeypatch.setattr(serving_mod, "run_queries_auto", wedged)
    return in_execute, release


@resilience
def test_batcher_follower_times_out_behind_wedged_leader(
    dindex, monkeypatch
):
    """A wedged kernel launch must not strand followers forever: the
    follower's wait is bounded and raises BatchTimeout (the seed's
    unbounded ``me.event.wait()`` hang, fixed)."""
    from sbeacon_tpu.serving import MicroBatcher

    shard, di = dindex
    spec = _spec(shard)
    # a long follower-wait window keeps the leader claimed while the
    # follower queues behind it; the launch itself is wedged too
    mb = MicroBatcher(max_batch=64, max_wait_ms=400)
    _in_execute, release = _wedge_launches(monkeypatch)

    leader_done = []

    def leader():
        leader_done.append(
            mb.submit(di, spec, window_cap=256, record_cap=64)
        )

    lt = threading.Thread(target=leader)
    lt.start()
    acc = mb._accum(di, (256, 64))
    t_end = time.time() + 5
    while time.time() < t_end and not acc.leader_active:
        time.sleep(0.005)
    assert acc.leader_active  # the thread above holds leadership
    t0 = time.perf_counter()
    with pytest.raises(BatchTimeout):
        mb.submit(
            di, spec, window_cap=256, record_cap=64, timeout_s=0.2
        )
    assert time.perf_counter() - t0 < 5.0
    release.set()
    lt.join(10)
    assert not lt.is_alive()
    assert leader_done and leader_done[0].exists is not None
    assert mb.occupancy()["timeouts"] == 1
    # accumulator healthy again: a fresh submit completes
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    assert got.exists is not None
    assert acc.leader_active is False and acc.items == []


@resilience
def test_batcher_leader_bounded_on_wedged_launch(dindex, monkeypatch):
    """The LEADER's wait is bounded too: a wedged kernel launch fails
    the leading request with 503/504 (launch dispatched to the launcher
    pool) instead of stranding the request thread — and its admission
    slot — until the device recovers."""
    from sbeacon_tpu.serving import MicroBatcher

    shard, di = dindex
    spec = _spec(shard)
    mb = MicroBatcher(max_batch=8, max_wait_ms=0)
    _in_execute, release = _wedge_launches(monkeypatch)
    t0 = time.perf_counter()
    with pytest.raises(BatchTimeout):
        mb.submit(di, spec, window_cap=256, record_cap=64, timeout_s=0.3)
    assert time.perf_counter() - t0 < 5.0
    assert mb.occupancy()["timeouts"] == 1
    # same wedge under a request deadline: 504 semantics
    with deadline_scope(Deadline.after(0.2)):
        with pytest.raises(DeadlineExceeded):
            mb.submit(di, spec, window_cap=256, record_cap=64)
    release.set()
    time.sleep(0.3)  # drain the two background launches
    acc = mb._accum(di, (256, 64))
    assert acc.leader_active is False and acc.items == []
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    assert got.exists is not None  # accumulator fully recovered
    mb.close()


@resilience
def test_leader_hands_off_backlog_once_served(dindex, monkeypatch):
    """Under sustained backlog the leader must return the moment its
    own answer is in — remaining batches drain on a transient daemon
    thread, not on the leading request's clock (or admission slot)."""
    import sbeacon_tpu.serving as serving_mod

    shard, di = dindex
    spec = _spec(shard)
    orig = serving_mod.run_queries_auto
    launch_s = 0.4
    window_s = 1.0

    def slow(index, queries, **kw):
        time.sleep(launch_s)
        return orig(index, queries, **kw)

    monkeypatch.setattr(serving_mod, "run_queries_auto", slow)
    # long follower window + max_batch smaller than the backlog: the
    # leader pops its batch with items REMAINING (leadership retained),
    # the sustained-load regime the handoff exists for
    mb = serving_mod.MicroBatcher(
        max_batch=2, max_wait_ms=window_s * 1e3
    )

    t_leader = []

    def leader():
        t0 = time.perf_counter()
        r = mb.submit(di, spec, window_cap=256, record_cap=64)
        t_leader.append((time.perf_counter() - t0, r))

    lt = threading.Thread(target=leader)
    lt.start()
    acc = mb._accum(di, (256, 64))
    t_end = time.time() + 5
    while time.time() < t_end and not acc.leader_active:
        time.sleep(0.005)
    assert acc.leader_active  # inside the follower window
    n_follow = 4
    results = [None] * n_follow

    def follower(i):
        results[i] = mb.submit(di, spec, window_cap=256, record_cap=64)

    fts = [
        threading.Thread(target=follower, args=(i,))
        for i in range(n_follow)
    ]
    for t in fts:
        t.start()
    # all 5 entries queued well inside the 1 s window
    t_end = time.time() + window_s * 0.9
    while time.time() < t_end and len(acc.items) < 1 + n_follow:
        time.sleep(0.005)
    assert len(acc.items) == 1 + n_follow
    lt.join(10)
    assert not lt.is_alive()
    took, res = t_leader[0]
    assert res.exists is not None
    # leader's own batch (2 of the 5 entries) completes after
    # window + launch_s; a full serial drain is window + 3 * launch_s.
    # The handoff must bring the leader back well before the drain.
    assert took < window_s + 2.2 * launch_s, took
    for t in fts:
        t.join(15)
        assert not t.is_alive()
    assert all(r is not None and r.exists is not None for r in results)
    # the transient drainer died with the backlog; accumulator is clean
    t_end = time.time() + 5
    while time.time() < t_end and acc.leader_active:
        time.sleep(0.01)
    assert acc.leader_active is False and acc.items == []


@resilience
def test_batcher_leader_bounded_on_wedged_fetch(dindex, monkeypatch):
    """The async launch/fetch split adds a second stage that can wedge
    (device_get never returning): the leader's wait must be bounded
    there too, and the accumulator must recover once the fetch frees."""
    import sbeacon_tpu.ops.kernel as kernel_mod
    from sbeacon_tpu.serving import MicroBatcher

    shard, di = dindex
    spec = _spec(shard)
    release = threading.Event()
    orig = kernel_mod.PendingQueryResults.fetch

    def wedged(self):
        assert release.wait(15), "test deadlock"
        return orig(self)

    monkeypatch.setattr(kernel_mod.PendingQueryResults, "fetch", wedged)
    mb = MicroBatcher(max_batch=8, max_wait_ms=0)
    t0 = time.perf_counter()
    with pytest.raises(BatchTimeout):
        mb.submit(di, spec, window_cap=256, record_cap=64, timeout_s=0.3)
    assert time.perf_counter() - t0 < 5.0
    release.set()
    monkeypatch.setattr(kernel_mod.PendingQueryResults, "fetch", orig)
    time.sleep(0.3)  # drain the background fetch
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    assert got.exists is not None  # accumulator fully recovered
    mb.close()


@resilience
def test_batcher_refuses_launch_for_expired_batch(dindex):
    """A batch whose every member is already past its deadline must not
    launch at all — and each waiter gets DeadlineExceeded."""
    from sbeacon_tpu.serving import MicroBatcher

    shard, di = dindex
    spec = _spec(shard)
    mb = MicroBatcher(max_batch=8, max_wait_ms=0)
    with deadline_scope(Deadline.after(0.001)):
        time.sleep(0.01)  # expired before submit even queues
        with pytest.raises(DeadlineExceeded):
            mb.submit(di, spec, window_cap=256, record_cap=64)
    occ = mb.occupancy()
    assert occ["launches"] == 0
    assert occ["expired"] == 1
    # no ambient deadline: same submit launches fine
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    assert got.exists is not None
    assert mb.occupancy()["launches"] == 1


@resilience
def test_batcher_ambient_deadline_bounds_follower_wait(
    dindex, monkeypatch
):
    """The HTTP-layer deadline propagates into the follower wait via the
    thread-local scope — no per-call plumbing."""
    from sbeacon_tpu.serving import MicroBatcher

    shard, di = dindex
    spec = _spec(shard)
    mb = MicroBatcher(max_batch=64, max_wait_ms=400)
    _in_execute, release = _wedge_launches(monkeypatch)
    lt = threading.Thread(
        target=lambda: mb.submit(di, spec, window_cap=256, record_cap=64)
    )
    lt.start()
    acc = mb._accum(di, (256, 64))
    t_end = time.time() + 5
    while time.time() < t_end and not acc.leader_active:
        time.sleep(0.005)
    assert acc.leader_active
    with deadline_scope(Deadline.after(0.2)):
        # the REQUEST deadline (not the local batch timeout) lapsed:
        # the client gets 504 semantics, matching every other checkpoint
        with pytest.raises(DeadlineExceeded):
            mb.submit(di, spec, window_cap=256, record_cap=64)
    assert mb.occupancy()["expired"] == 1
    assert mb.occupancy()["timeouts"] == 0
    release.set()
    lt.join(10)
    assert not lt.is_alive()


# -- async query runner -------------------------------------------------------


class _BlockingEngine:
    """engine.search blocks until released; config satisfies the runner."""

    def __init__(self):
        from sbeacon_tpu.config import BeaconConfig

        self.config = BeaconConfig()
        self.release = threading.Event()
        self.calls = 0

    def index_fingerprint(self):
        return "fp"

    def search(self, payload):
        self.calls += 1
        assert self.release.wait(20), "test deadlock"
        return []


def _payload(i: int, dataset_ids=None):
    from sbeacon_tpu.payloads import VariantQueryPayload

    return VariantQueryPayload(
        dataset_ids=dataset_ids or [f"d{i}"],
        reference_name="1",
        start_min=i + 1,
        start_max=i + 2,
        end_min=1,
        end_max=1 << 30,
    )


@resilience
def test_runner_bounded_pool_sheds_not_spawns():
    from sbeacon_tpu.query_jobs import (
        AsyncQueryRunner,
        JobStatus,
        QueryJobTable,
    )

    eng = _BlockingEngine()
    table = QueryJobTable(":memory:")
    runner = AsyncQueryRunner(eng, table, workers=2, max_pending=2)
    try:
        assert runner.workers == 2
        q1, s1 = runner.submit(_payload(1))
        q2, s2 = runner.submit(_payload(2))
        assert s1 is JobStatus.RUNNING and s2 is JobStatus.RUNNING
        # identical query coalesces, consumes no slot, is never shed
        q1b, s1b = runner.submit(_payload(1))
        assert (q1b, s1b) == (q1, JobStatus.RUNNING)
        # a THIRD distinct query fast-fails instead of spawning thread 3
        with pytest.raises(Overloaded) as ei:
            runner.submit(_payload(3))
        assert ei.value.status == 429
        assert runner.metrics()["shed"] == 1
        assert runner.metrics()["active"] == 2
        eng.release.set()
        deadline = time.time() + 10
        while runner.metrics()["active"] and time.time() < deadline:
            time.sleep(0.01)
        assert runner.metrics()["active"] == 0
        # capacity restored: the shed query is accepted now
        q3, s3 = runner.submit(_payload(3))
        assert s3 in (JobStatus.RUNNING, JobStatus.COMPLETED)
        assert runner.result(q1, wait_s=5.0) == []
    finally:
        eng.release.set()
        runner.close()
        table.close()


@resilience
def test_runner_releases_slot_when_claim_fails(monkeypatch):
    """A claim that cannot be handed to the pool (it was shut down under
    a late submit) must leak neither the reserved slot — leaks would
    eventually shed every submit against an idle pool — nor the claim:
    the id must not read RUNNING with nobody executing it."""
    from sbeacon_tpu.query_jobs import (
        AsyncQueryRunner,
        JobStatus,
        QueryJobTable,
    )

    eng = _BlockingEngine()
    table = QueryJobTable(":memory:")
    runner = AsyncQueryRunner(eng, table, workers=1, max_pending=1)
    try:
        monkeypatch.setattr(
            runner._pool,
            "submit",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("cannot schedule new futures after shutdown")
            ),
        )
        for _ in range(3):
            with pytest.raises(RuntimeError):
                runner.submit(_payload(7))
        assert runner.metrics()["active"] == 0  # no leaked reservations
        monkeypatch.undo()
        qid, status = runner.submit(_payload(7))  # capacity and id intact
        assert status is JobStatus.RUNNING and eng.calls <= 1
        eng.release.set()
        assert runner.result(qid, wait_s=5.0) == []
    finally:
        eng.release.set()
        runner.close()
        table.close()


@resilience
def test_runner_single_purge_sweeper(monkeypatch):
    """The sweep runs on the one writer thread: a slow one (WAL
    checkpoint on a busy disk) is never stacked with a second, and holds
    up no request, only the rows behind it."""
    from sbeacon_tpu.query_jobs import AsyncQueryRunner, QueryJobTable

    eng = _BlockingEngine()
    eng.release.set()
    table = QueryJobTable(":memory:")
    runner = AsyncQueryRunner(eng, table, workers=2, max_pending=4)
    gate = threading.Event()
    try:
        entered = threading.Event()
        sweeps = []

        def slow_purge():
            sweeps.append(threading.current_thread())
            entered.set()
            assert gate.wait(10), "test deadlock"
            return 0

        def written(n):
            deadline = time.time() + 10
            while runner._n_persisted_jobs < n and time.time() < deadline:
                time.sleep(0.005)
            return runner._n_persisted_jobs

        monkeypatch.setattr(table, "purge_expired", slow_purge)
        runner._next_sweep = 0.0  # interval lapsed
        q0, _ = runner.submit(_payload(0))  # its row wakes the writer
        assert entered.wait(5)
        assert written(1) == 1  # stored before the sweep began
        for i in range(1, 6):  # the sweep is parked: requests answer
            qid, _ = runner.submit(_payload(i))
            assert runner.result(qid, wait_s=5.0) == []
        assert sweeps == [runner._writer]  # no second sweeper stacked
        assert runner._n_persisted_jobs == 1  # their rows wait behind it
        gate.set()
        assert written(6) == 6
        # sweep finished: the next lapsed interval starts a new one
        runner._next_sweep = 0.0
        runner.submit(_payload(6))
        assert written(7) == 7
        deadline = time.time() + 10
        while len(sweeps) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert sweeps == [runner._writer] * 2
    finally:
        gate.set()
        runner.close()
        table.close()


@resilience
def test_job_wait_clamped_by_ambient_deadline():
    from sbeacon_tpu.query_jobs import QueryJobTable

    table = QueryJobTable(":memory:")
    try:
        claim = table.start("q1", fan_out=1)
        assert claim
        t0 = time.perf_counter()
        with deadline_scope(Deadline.after(0.1)):
            assert table.wait("q1", timeout_s=30.0) is False
        assert time.perf_counter() - t0 < 5.0
    finally:
        table.close()


# -- dispatch circuit breaker -------------------------------------------------


def _dispatch_engine(post, clock, *, threshold=3, retries=0):
    from sbeacon_tpu.config import BeaconConfig, ResilienceConfig
    from sbeacon_tpu.parallel.dispatch import DistributedEngine

    def get(url, timeout_s, headers=None):
        return 200, {"datasets": ["ds"], "fingerprint": "f"}

    br = CircuitBreaker(
        failure_threshold=threshold,
        reset_timeout_s=10.0,
        half_open_probes=1,
        clock=clock,
    )
    # strict mode: these tests assert the raise semantics of a
    # single-replica fleet (partial-results degradation is covered by
    # tests/test_replica_routing.py)
    return DistributedEngine(
        ["http://w1:1"],
        retries=retries,
        post=post,
        get=get,
        breaker=br,
        config=BeaconConfig(
            resilience=ResilienceConfig(partial_results=False)
        ),
    )


@resilience
def test_dispatch_breaker_opens_fast_fails_and_recovers():
    from sbeacon_tpu.parallel.dispatch import WorkerError

    clock = [0.0]
    posts = []
    healthy = [False]

    def post(url, doc, timeout_s, headers=None):
        posts.append(url)
        if not healthy[0]:
            raise ConnectionError("injected: worker down")
        return 200, {"responses": []}

    eng = _dispatch_engine(post, lambda: clock[0])
    try:
        pay = _payload(0, dataset_ids=["ds"])
        for _ in range(3):
            with pytest.raises(WorkerError):
                eng.search(pay)
        assert eng.breaker.state("http://w1:1") == "open"
        n_posts = len(posts)
        # open circuit: fast-fail without touching the worker
        with pytest.raises(CircuitOpen) as ei:
            eng.search(pay)
        assert ei.value.status == 503
        assert len(posts) == n_posts
        assert eng.breaker.metrics()["http://w1:1"]["opens"] == 1
        # reset window lapses; worker recovered: half-open probe closes
        clock[0] = 10.0
        healthy[0] = True
        assert eng.search(pay) == []
        assert eng.breaker.state("http://w1:1") == "closed"
        assert eng.search(pay) == []  # and stays closed
    finally:
        eng.close()


@resilience
def test_dispatch_hung_worker_bounded_by_deadline():
    """A hung worker (injected via the seeded fault plan) resolves as a
    deadline error within the request's bound, not after timeout_s —
    and the worker-call timeout itself is deadline-clamped across the
    scatter-pool thread boundary."""
    faults.install(
        {
            "seed": 3,
            "rules": [
                {"site": "worker.http", "kind": "hang", "ms": 700.0}
            ],
        }
    )
    calls = []

    def post(url, doc, timeout_s, headers=None):
        calls.append(timeout_s)
        return 200, {"responses": []}

    eng = _dispatch_engine(post, time.monotonic)
    try:
        pay = _payload(0, dataset_ids=["ds"])
        t0 = time.perf_counter()
        with deadline_scope(Deadline.after(0.25)):
            with pytest.raises(DeadlineExceeded):
                eng.search(pay)
        took = time.perf_counter() - t0
        # resolved at ~the deadline, NOT after the 700 ms hang
        assert took < 0.65, took
        time.sleep(0.8)  # let the hung pool call finish (not hung)
        assert all(t is not None and t <= 0.25 for t in calls), calls
    finally:
        eng.close()


# -- fault injection ----------------------------------------------------------


@resilience
def test_fault_injector_is_deterministic():
    plan = {
        "seed": 42,
        "rules": [
            {"site": "kernel.launch", "kind": "error", "rate": 0.3}
        ],
    }

    def pattern():
        inj = faults.install(plan)
        out = []
        for _ in range(50):
            try:
                faults.fault_point("kernel.launch")
                out.append(0)
            except faults.FaultError:
                out.append(1)
        assert inj.stats()["kernel.launch[0]"]["activations"] == sum(out)
        return out

    first = pattern()
    assert 0 < sum(first) < 50  # rate actually partial
    assert pattern() == first  # same plan, same sequence — every run


@resilience
def test_fault_rule_after_count_and_match():
    faults.install(
        {
            "seed": 1,
            "rules": [
                {
                    "site": "worker.http",
                    "kind": "error",
                    "rate": 1.0,
                    "after": 2,
                    "count": 2,
                    "match": "w1",
                }
            ],
        }
    )
    hits = []
    for _ in range(8):
        try:
            faults.fault_point("worker.http", "http://w1:1")
            hits.append(0)
        except faults.FaultError:
            hits.append(1)
    # first 2 skipped (after), next 2 fire (count), rest exhausted
    assert hits == [0, 0, 1, 1, 0, 0, 0, 0]
    faults.fault_point("worker.http", "http://other:1")  # match filters
    faults.fault_point("kernel.launch")  # unrelated site untouched


@resilience
def test_fault_plan_env_install(tmp_path):
    plan_file = tmp_path / "plan.json"
    plan_file.write_text(
        '{"seed": 5, "rules": [{"site": "sqlite.commit", "kind": '
        '"latency", "ms": 1.0}]}'
    )
    inj = faults.install_from_env({"BEACON_FAULT_PLAN": f"@{plan_file}"})
    assert inj is not None
    faults.fault_point("sqlite.commit")
    assert inj.stats()["sqlite.commit[0]"]["hits"] == 1
    faults.uninstall()
    assert faults.install_from_env({}) is None


# -- API surface --------------------------------------------------------------


@pytest.fixture()
def app():
    from sbeacon_tpu.api import BeaconApp

    return BeaconApp()


@resilience
def test_probes_and_metrics_bypass_admission(app):
    status, body = app.handle("GET", "/health")
    assert status == 200 and body["ok"] is True
    status, body = app.handle("GET", "/ready")
    assert status == 200 and body["ready"] is True
    assert "shards" in body and "inFlight" in body
    status, body = app.handle("GET", "/metrics")
    assert status == 200
    assert "admission" in body and "runner" in body and "batcher" in body

    app.admission = AdmissionController(1)
    with app.admission.admit():  # server fully saturated
        status, body = app.handle("GET", "/info")
        assert status == 429
        assert body["error"]["errorCode"] == 429
        assert body["retryAfterSeconds"] == 1.0
        # probes still answer — that is their whole job
        assert app.handle("GET", "/health")[0] == 200
        assert app.handle("GET", "/ready")[0] == 200
        assert app.handle("GET", "/metrics")[0] == 200
        assert app.admission.metrics()["shed"] == 1
    status, _ = app.handle("GET", "/info")
    assert status == 200

    app.ready = False  # drain: readiness flips, liveness stays up
    status, body = app.handle("GET", "/ready")
    assert status == 503 and body["ready"] is False
    assert app.handle("GET", "/health")[0] == 200


@resilience
def test_deadline_header_parse_and_default(app):
    # <=0 must not silently disable the operator's configured default
    for bad in ("nope", "nan", "inf", "-inf", "0", "-1"):
        status, body = app.handle(
            "GET", "/info", headers={"X-Beacon-Deadline": bad}
        )
        assert status == 400, bad
        assert "X-Beacon-Deadline" in body["error"]["errorMessage"]
    status, _ = app.handle(
        "GET", "/info", headers={"x-beacon-deadline": "5.0"}
    )
    assert status == 200
    # config default applies to normal routes, not /submit (bulk
    # ingest is a batch job) — an explicit header still bounds /submit
    assert app._request_deadline("g_variants", {}).remaining() is not None
    assert app._request_deadline("submit", {}) is NO_DEADLINE
    bounded = app._request_deadline("submit", {"X-Beacon-Deadline": "9"})
    assert bounded.remaining() is not None


@resilience
def test_resilience_error_envelope_mapping(app):
    """Typed failures raised anywhere under _route map to their status
    with a well-formed Beacon error envelope."""
    for exc, want in (
        (Overloaded("full", retry_after_s=2.0), 429),
        (BatchTimeout("wedged"), 503),
        (CircuitOpen("open"), 503),
        (DeadlineExceeded("late"), 504),
        (TimeoutError("engine timeout"), 504),
    ):

        def boom(*a, **k):
            raise exc

        orig = app._route
        app._route = boom
        try:
            status, body = app.handle("GET", "/info")
        finally:
            app._route = orig
        assert status == want, exc
        assert body["error"]["errorCode"] == want
        assert body["error"]["errorMessage"]
        if isinstance(exc, Overloaded):
            assert body["retryAfterSeconds"] == 2.0


# -- end-to-end ---------------------------------------------------------------


def _records():
    from sbeacon_tpu.testing import random_records

    rng = random.Random(5)
    return random_records(rng, chrom="21", n=300, n_samples=2)


def _gv_query(rec, k=0):
    return {
        "query": {
            "requestedGranularity": "boolean",
            "requestParameters": {
                "assemblyId": "GRCh38",
                "referenceName": "21",
                "start": [max(0, rec.pos - 1 - k)],
                "end": [rec.pos + len(rec.ref) + 5 + k],
                "alternateBases": "N",
            },
        }
    }


def _shard(recs):
    from sbeacon_tpu.index.columnar import build_index

    return build_index(
        recs,
        dataset_id="rz",
        vcf_location="synthetic://rz",
        sample_names=["A", "B"],
    )


def _register_dataset(app):
    app.store.upsert(
        "datasets",
        [
            {
                "id": "rz",
                "name": "rz",
                "_assemblyId": "GRCh38",
                "_vcfLocations": ["synthetic://rz"],
            }
        ],
    )


@resilience
def test_deadline_expiry_mid_query_maps_to_504(tmp_path):
    """End-to-end: a kernel launch slower than the request deadline
    surfaces as a 504 Beacon error envelope, within deadline + slack."""
    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.config import (
        BeaconConfig,
        EngineConfig,
        StorageConfig,
    )

    recs = _records()
    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "d"),
        engine=EngineConfig(use_mesh=False, microbatch=True),
    )
    cfg.storage.ensure()
    app = BeaconApp(cfg)
    app.engine.add_index(_shard(recs))
    _register_dataset(app)
    status, _ = app.handle("POST", "/g_variants", body=_gv_query(recs[0]))
    assert status == 200  # warm: only the injected latency is slow below
    faults.install(
        {
            "seed": 9,
            "rules": [
                {"site": "kernel.launch", "kind": "latency", "ms": 1500.0}
            ],
        }
    )
    t0 = time.perf_counter()
    status, body = app.handle(
        "POST",
        "/g_variants",
        body=_gv_query(recs[1], k=1),
        headers={"X-Beacon-Deadline": "0.4"},
    )
    took = time.perf_counter() - t0
    assert status == 504, body
    assert body["error"]["errorCode"] == 504
    assert took < 0.4 + 1.0, took
    time.sleep(1.3)  # drain the injected sleep before teardown


@pytest.mark.slow
def test_chaos_soak_no_hung_threads(tmp_path):
    """Chaos soak: a coordinator + one worker host under 64 concurrent
    deadline-carrying clients, with a seeded plan injecting hung worker
    calls, kernel-launch exceptions, and slow sqlite commits. Every
    request must resolve (result / 429 / error envelope); probes must
    answer mid-run; breaker state must be observable; and no thread may
    stay permanently blocked after the run."""
    import http.client
    import json as json_mod

    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.api.server import start_background
    from sbeacon_tpu.config import (
        BeaconConfig,
        EngineConfig,
        ResilienceConfig,
        StorageConfig,
    )
    from sbeacon_tpu.engine import VariantEngine
    from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer

    recs = _records()
    wcfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "w"),
        engine=EngineConfig(use_mesh=False, microbatch=True),
    )
    weng = VariantEngine(wcfg)
    weng.add_index(_shard(recs))
    worker = WorkerServer(weng).start_background()

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "c"),
        engine=EngineConfig(use_mesh=False, microbatch=True),
        resilience=ResilienceConfig(
            batch_timeout_s=5.0, max_in_flight=8, shed_retry_after_s=0.5
        ),
    )
    cfg.storage.ensure()
    dist = DistributedEngine(
        [worker.address],
        local=VariantEngine(cfg),
        config=cfg,
        retries=1,
        timeout_s=10.0,
        max_threads=16,
    )
    app = BeaconApp(cfg, engine=dist)
    _register_dataset(app)
    status, _ = app.handle("POST", "/g_variants", body=_gv_query(recs[0]))
    assert status == 200  # warm + routes discovered before the chaos

    faults.install(
        {
            "seed": 1234,
            "rules": [
                # the hung worker: the coordinator-side call stalls
                # well past the request deadline
                {
                    "site": "worker.http",
                    "kind": "hang",
                    "rate": 0.15,
                    "ms": 2500.0,
                },
                # kernel-launch exceptions on the worker's engine
                {"site": "kernel.launch", "kind": "error", "rate": 0.25},
                # slow job-table commits on the coordinator
                {
                    "site": "sqlite.commit",
                    "kind": "latency",
                    "rate": 0.5,
                    "ms": 30.0,
                },
            ],
        }
    )

    server, _t = start_background(app)
    port = server.server_address[1]
    deadline_s = 2.0
    n_clients, per_client = 64, 2
    statuses: list[int] = []
    latencies: list[float] = []
    retry_after_seen: list[str] = []
    bad_envelopes: list[dict] = []
    lock = threading.Lock()
    start = threading.Barrier(n_clients + 1)
    threads_before = set(threading.enumerate())

    def client(k: int):
        rng = random.Random(1000 + k)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        start.wait()
        for i in range(per_client):
            q = _gv_query(recs[rng.randrange(len(recs))], k=k * 31 + i)
            t0 = time.perf_counter()
            conn.request(
                "POST",
                "/g_variants",
                body=json_mod.dumps(q).encode(),
                headers={
                    "Content-Type": "application/json",
                    "X-Beacon-Deadline": str(deadline_s),
                },
            )
            r = conn.getresponse()
            body = json_mod.loads(r.read())
            took = time.perf_counter() - t0
            ok_shape = "responseSummary" in body or "error" in body
            with lock:
                statuses.append(r.status)
                latencies.append(took)
                if r.status == 429 and r.getheader("Retry-After"):
                    retry_after_seen.append(r.getheader("Retry-After"))
                if not ok_shape:
                    bad_envelopes.append(body)
        conn.close()

    threads = [
        threading.Thread(target=client, args=(k,), daemon=True)
        for k in range(n_clients)
    ]
    for t in threads:
        t.start()
    start.wait()
    # probes + metrics answer while the chaos runs
    probe = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    for path in ("/health", "/ready", "/metrics"):
        probe.request("GET", path)
        r = probe.getresponse()
        assert r.status == 200, path
        r.read()
    probe.close()
    for t in threads:
        t.join(180)
        assert not t.is_alive(), "client thread hung"

    assert len(statuses) == n_clients * per_client
    assert set(statuses) <= {200, 429, 500, 503, 504}, set(statuses)
    assert statuses.count(200) > 0  # chaos didn't kill everything
    assert not bad_envelopes, bad_envelopes[:2]
    if 429 in statuses:
        assert retry_after_seen  # the backoff header rode along
    # every request resolved within the deadline envelope. The +1 s
    # acceptance headroom assumes out-of-process clients; these 64
    # client threads share one interpreter (and usually one core) with
    # the server, so scheduling delay is billed to the client clock —
    # allow GIL slack on top of the protocol bound.
    bound = deadline_s + 1.0 + 2.0
    late = [x for x in latencies if x > bound]
    assert not late, (late, sorted(latencies)[-5:])

    # faults actually fired, and breaker state is observable in metrics
    _, metrics = app.handle("GET", "/metrics")
    fired = sum(
        f["activations"] for f in metrics.get("faults", {}).values()
    )
    assert fired > 0, metrics
    assert worker.address in metrics.get("breaker", {}), metrics

    server.shutdown()
    worker.shutdown()

    # no permanently blocked threads: the handler pools drain idle and
    # any injected hang (2.5 s) finishes; whatever outlives the run must
    # be reusable pool/server infrastructure, not a stuck request
    t_end = time.time() + 30
    while time.time() < t_end:
        if (
            app.query_runner.metrics()["active"] == 0
            and app.admission.metrics()["in_flight"] == 0
        ):
            break
        time.sleep(0.2)
    assert app.query_runner.metrics()["active"] == 0
    assert app.admission.metrics()["in_flight"] == 0
    allowed = (
        "dispatch",
        "query-runner",
        "query-jobs-purge",
        "kernel-launch",
        # the batcher's fetcher pool grows lazily under load; its idle
        # threads are reusable infrastructure like kernel-launch's
        # (more chaos requests now SUCCEED via failover/partial
        # results, so the pool reaches its full size mid-soak)
        "kernel-fetch",
        "Thread-",
    )
    t_end = time.time() + 20
    while time.time() < t_end:
        stray = [
            t
            for t in threading.enumerate()
            if t not in threads_before
            and t.is_alive()
            and not t.name.startswith(allowed)
            and t is not threading.current_thread()
        ]
        if not stray:
            break
        time.sleep(0.2)
    assert not stray, [t.name for t in stray]
