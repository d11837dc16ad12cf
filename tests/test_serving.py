"""Serving micro-batcher: correctness under concurrency, bucketing,
error propagation, and end-to-end equivalence with unbatched execution."""

import random
import threading

import numpy as np
import pytest

from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import DeviceIndex, QuerySpec, run_queries
from sbeacon_tpu.serving import MicroBatcher
from sbeacon_tpu.testing import random_records


@pytest.fixture(scope="module")
def dindex():
    rng = random.Random(7)
    recs = random_records(rng, chrom="1", n=300, n_samples=2)
    shard = build_index(
        recs, dataset_id="ds", vcf_location="v", sample_names=["S0", "S1"]
    )
    return shard, DeviceIndex(shard, pad_unit=1024)


def specs_for(shard, n):
    rng = random.Random(n)
    pos = shard.cols["pos"]
    out = []
    for i in range(n):
        p = int(pos[rng.randrange(len(pos))])
        out.append(
            QuerySpec("1", max(1, p - 5), p + 5, 1, 1 << 30, alternate_bases="N")
        )
    return out


def test_batch_tiers_pad_and_trim():
    """run_queries pads to fixed BATCH_TIERS (repeating query 0) and
    trims every output back to the logical batch — the shape-bucketing
    the batcher used to pre-do (now one place only)."""
    import random

    from sbeacon_tpu.index import build_index
    from sbeacon_tpu.ops import DeviceIndex
    from sbeacon_tpu.ops.kernel import (
        BATCH_TIERS,
        QuerySpec,
        run_queries,
    )
    from sbeacon_tpu.testing import random_records

    assert BATCH_TIERS == (8, 64, 512, 2048)
    rng = random.Random(3)
    recs = random_records(rng, chrom="1", n=200, n_samples=4)
    shard = build_index(recs, dataset_id="bt")
    dindex = DeviceIndex(shard, pad_unit=1024)
    pos = shard.cols["pos"]
    specs = [
        QuerySpec(
            "1",
            int(pos[rng.randrange(shard.n_rows)]),
            int(pos[rng.randrange(shard.n_rows)]) + 200,
            1,
            1 << 30,
            alternate_bases="N",
        )
        for _ in range(11)  # pads to the 64 tier
    ]
    got = run_queries(dindex, specs, window_cap=256, record_cap=32)
    assert len(got.exists) == 11  # trimmed, not tier-sized
    # per-query answers must be independent of tier padding: compare
    # against each query answered alone (pads to the 8 tier)
    for i, s in enumerate(specs):
        one = run_queries(dindex, [s], window_cap=256, record_cap=32)
        assert bool(one.exists[0]) == bool(got.exists[i])
        assert int(one.call_count[0]) == int(got.call_count[i])
        assert int(one.all_alleles_count[0]) == int(
            got.all_alleles_count[i]
        )


def test_single_submit_matches_direct(dindex):
    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    ref = run_queries(di, [spec], window_cap=256, record_cap=64)
    assert got.exists[0] == ref.exists[0]
    assert got.call_count[0] == ref.call_count[0]
    assert got.all_alleles_count[0] == ref.all_alleles_count[0]
    np.testing.assert_array_equal(got.rows[0], ref.rows[0])


def test_concurrent_submits_match_direct_and_batch(dindex):
    shard, di = dindex
    n = 32
    specs = specs_for(shard, n)
    ref = run_queries(di, specs, window_cap=256, record_cap=64)
    mb = MicroBatcher(max_batch=64, max_wait_ms=20)
    results = [None] * n
    barrier = threading.Barrier(n)

    def go(i):
        barrier.wait()
        results[i] = mb.submit(di, specs[i], window_cap=256, record_cap=64)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(n):
        assert results[i].exists[0] == ref.exists[i], i
        assert results[i].call_count[0] == ref.call_count[i], i
        np.testing.assert_array_equal(results[i].rows[0], ref.rows[i])


def test_max_batch_overflow_drains(dindex):
    """More waiters than max_batch: the leader drains in several rounds."""
    shard, di = dindex
    n = 20
    specs = specs_for(shard, n)
    ref = run_queries(di, specs, window_cap=256, record_cap=64)
    mb = MicroBatcher(max_batch=8, max_wait_ms=10)
    results = [None] * n
    barrier = threading.Barrier(n)

    def go(i):
        barrier.wait()
        results[i] = mb.submit(di, specs[i], window_cap=256, record_cap=64)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(n):
        assert results[i].exists[0] == ref.exists[i], i


def test_error_propagates_to_all_waiters(dindex):
    shard, di = dindex
    mb = MicroBatcher(max_batch=8, max_wait_ms=0)

    class BadIndex:
        """Object lacking .arrays — run_queries raises for every batch."""

        n_iters = 4

    (spec,) = specs_for(shard, 1)
    with pytest.raises(Exception):
        mb.submit(BadIndex(), spec, window_cap=256, record_cap=64)
    # the accumulator must be reusable after a failed round
    with pytest.raises(Exception):
        mb.submit(BadIndex(), spec, window_cap=256, record_cap=64)


def test_engine_batched_equals_unbatched():
    """End-to-end: identical search responses with microbatch on/off."""
    import dataclasses

    from sbeacon_tpu.config import BeaconConfig, EngineConfig
    from sbeacon_tpu.engine import VariantEngine
    from sbeacon_tpu.payloads import VariantQueryPayload

    rng = random.Random(3)
    recs = random_records(rng, chrom="1", n=200, n_samples=2)
    shard = build_index(
        recs, dataset_id="ds", vcf_location="v", sample_names=["S0", "S1"]
    )
    pay = VariantQueryPayload(
        dataset_ids=["ds"],
        reference_name="1",
        start_min=1,
        start_max=1 << 30,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        include_datasets="HIT",
    )
    on = VariantEngine(BeaconConfig(engine=EngineConfig(microbatch=True)))
    off = VariantEngine(BeaconConfig(engine=EngineConfig(microbatch=False)))
    on.add_index(shard)
    off.add_index(shard)
    r_on = on.search(pay)
    r_off = off.search(pay)
    assert len(r_on) == len(r_off) == 1
    assert r_on[0].dumps() == r_off[0].dumps()


def test_leader_death_releases_leadership_and_fails_followers(dindex):
    """If the leader dies with an exception _execute doesn't swallow
    (e.g. KeyboardInterrupt in the follower-wait window), leadership must
    be released and queued followers unblocked with the error — otherwise
    they (and every future submit) hang on event.wait() forever."""
    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)

    class Boom(BaseException):
        pass

    orig = MicroBatcher._execute

    def exploding(self, acc, batch, dindex_, window_cap, record_cap):
        raise Boom("leader died")

    MicroBatcher._execute = exploding
    try:
        with pytest.raises(Boom):
            mb.submit(di, spec, window_cap=256, record_cap=64)
    finally:
        MicroBatcher._execute = orig

    acc = mb._accum(di, (256, 64))
    assert acc.leader_active is False
    assert acc.items == []
    # accumulator is healthy again: a fresh submit leads and completes
    got = mb.submit(di, spec, window_cap=256, record_cap=64)
    ref = run_queries(di, [spec], window_cap=256, record_cap=64)
    assert got.exists[0] == ref.exists[0]


def test_concurrent_soak_batches_requests(tmp_path):
    """Concurrent keep-alive clients against the real HTTP server must
    coalesce into multi-query kernel launches (mean batch > 1) and
    every request answer 200 with sane latency percentiles."""
    import random
    import time

    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.api.server import start_background
    from sbeacon_tpu.config import BeaconConfig, EngineConfig, StorageConfig
    from sbeacon_tpu.genomics.tabix import ensure_index
    from sbeacon_tpu.genomics.vcf import write_vcf
    from sbeacon_tpu.harness.latency import Client
    from sbeacon_tpu.testing import random_records

    rng = random.Random(3)
    recs = random_records(rng, chrom="14", n=800, n_samples=2)
    vcf = tmp_path / "s.vcf.gz"
    write_vcf(vcf, recs, sample_names=["A", "B"])
    ensure_index(vcf)
    # a 25 ms batching window: on a one-core box request arrivals are
    # serialised, so the default 2 ms window sees at most one in-flight
    # query; the knob exists for this transport-vs-compute tradeoff
    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "b"),
        engine=EngineConfig(
            use_mesh=False, microbatch=True, microbatch_wait_ms=25.0
        ),
    )
    cfg.storage.ensure()
    app = BeaconApp(cfg)
    status, _ = app.handle(
        "POST",
        "/submit",
        body={
            "datasetId": "soak",
            "assemblyId": "GRCh38",
            "dataset": {"id": "soak", "name": "s"},
            "vcfLocations": [str(vcf)],
        },
    )
    assert status == 200
    server, _t = start_background(app)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    # one UNIQUE query per request: identical bodies are answered by
    # the runner's memory and never reach the batcher (that path is
    # tested elsewhere; the soak must measure kernel batching)
    n_clients, per_client = 8, 12
    queries = []
    for k in range(n_clients * per_client):
        rec = recs[rng.randrange(len(recs))]
        queries.append(
            {
                "query": {
                    "requestedGranularity": "boolean",
                    "requestParameters": {
                        "assemblyId": "GRCh38",
                        "referenceName": "14",
                        "start": [rec.pos - 1 - (k % 7)],
                        "end": [rec.pos + len(rec.ref) + 5 + k],
                        "alternateBases": "N",
                    },
                }
            }
        )

    batcher = app.engine._batcher
    before = batcher.occupancy()
    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(n_clients)

    def client(k: int):
        c = Client(base)
        start.wait()
        for body in queries[k * per_client : (k + 1) * per_client]:
            t0 = time.perf_counter()
            try:
                status, _ = c.post("/g_variants", body)
                if status != 200:
                    raise RuntimeError(f"status {status}")
            except Exception as e:  # noqa: BLE001 - recorded, not raised
                with lock:
                    errors.append(f"client{k}:{e}")
                continue
            took = time.perf_counter() - t0
            with lock:
                lat.append(took)

    threads = [
        threading.Thread(target=client, args=(k,), daemon=True)
        for k in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    after = batcher.occupancy()
    server.shutdown()

    # the box may be running unrelated heavy load; a stray transient
    # failure must not mask the batching evidence this test is for
    assert len(errors) <= 2, errors[:3]
    assert len(lat) >= 94
    lat.sort()
    assert 0 < lat[len(lat) // 2] <= lat[-1] < 60
    submits = after["submits"] - before["submits"]
    launches = sum(after["histogram"].values()) - sum(
        before["histogram"].values()
    )
    assert submits >= 94
    # contention must actually coalesce: strictly fewer launches than
    # submits, i.e. batching engaged (mean batch above 1)
    assert 0 < launches < submits


def test_engine_warmup_compiles_all_paths():
    """warmup() touches the scatter tiers, fused-plane programs, XLA
    batch tiers, and mesh pjit programs without error, and
    DistributedEngine delegates to its local engine."""
    import random

    from sbeacon_tpu.config import BeaconConfig, EngineConfig
    from sbeacon_tpu.engine import VariantEngine
    from sbeacon_tpu.index import build_index
    from sbeacon_tpu.ops.plane_kernel import PlaneDeviceIndex
    from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
    from sbeacon_tpu.parallel.dispatch import DistributedEngine
    from sbeacon_tpu.testing import random_records

    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False))
    )
    for d in range(2):
        rng = random.Random(40 + d)
        recs = random_records(rng, chrom="7", n=120, n_samples=4)
        shard = build_index(
            recs, dataset_id=f"w{d}", sample_names=[f"S{i}" for i in range(4)]
        )
        eng.add_prebuilt_index(
            shard, ScatterDeviceIndex(shard), planes=PlaneDeviceIndex(shard)
        )
    n = eng.warmup()
    # scatter tiers x exact x shapes + fused programs per shard + mesh
    assert n >= 10, n
    # repeat is cheap and idempotent
    assert eng.warmup() == n
    dist = DistributedEngine([], local=eng)
    # a coordinator warms the local engine's programs and none beside
    assert dist.warmup() == n
    dist.close()
    eng.close()


# -- backpressure before the pop: a launch takes what is waiting --------------


def _answers_equal(got, ref):
    assert bool(got.exists[0]) == bool(ref.exists[0])
    assert int(got.call_count[0]) == int(ref.call_count[0])
    assert int(got.all_alleles_count[0]) == int(ref.all_alleles_count[0])
    np.testing.assert_array_equal(got.rows[0], ref.rows[0])


def _hold_fetch(monkeypatch, seconds=None, gate=None):
    """The fetch stage held (a sleep, or until ``gate`` is set): stands
    for the time a launch keeps its fetch-pipeline slot on the chip's
    host. Returns the list of fetches entered."""
    import time

    import sbeacon_tpu.ops.kernel as kernel_mod

    entered = []
    orig = kernel_mod.PendingQueryResults.fetch

    def held(self):
        entered.append(self)
        if gate is not None:
            assert gate.wait(30), "test deadlock"
        else:
            time.sleep(seconds)
        return orig(self)

    monkeypatch.setattr(kernel_mod.PendingQueryResults, "fetch", held)
    return entered


def _wait_until(cond, seconds=10.0):
    import time

    t_end = time.time() + seconds
    while time.time() < t_end and not cond():
        time.sleep(0.002)
    assert cond()


def test_arrivals_behind_a_held_slot_ride_one_launch(dindex, monkeypatch):
    """16 threads x 40 submits on one accumulator with the fetch stage
    held 10 ms: whoever leads waits for the slot with the leadership
    claimed, so arrivals queue as followers and one pop takes them —
    launches are fewer than half the submits, every answer is that of
    the spec alone, and a pop always takes the head of the queue in
    order (first come first served)."""
    shard, di = dindex
    n_threads, per_thread = 16, 40
    pool = specs_for(shard, 48)
    refs = [
        run_queries(di, [s], window_cap=256, record_cap=64) for s in pool
    ]
    _hold_fetch(monkeypatch, seconds=0.010)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)
    acc = mb._accum(di, (256, 64))
    out_of_order = []
    orig_pop = mb._pop

    def spying_pop(acc_, dindex_, me):
        queued = list(acc_.items)  # arrivals only ever extend its end
        batch, more = orig_pop(acc_, dindex_, me)
        k = min(len(queued), len(batch))
        if any(a is not b for a, b in zip(queued[:k], batch[:k])):
            out_of_order.append((queued, batch))
        return batch, more

    mb._pop = spying_pop
    wrong = []

    def client(t):
        for j in range(per_thread):
            i = (t * per_thread + j * 7) % len(pool)
            got = mb.submit(di, pool[i], window_cap=256, record_cap=64)
            try:
                _answers_equal(got, refs[i])
            except AssertionError as e:
                wrong.append((t, j, e))

    threads = [
        threading.Thread(target=client, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    mb.close()
    occ = mb.occupancy()
    assert not wrong, wrong[:2]
    assert not out_of_order
    assert occ["submits"] == n_threads * per_thread
    assert sum(k * v for k, v in occ["histogram"].items()) == occ["submits"]
    assert occ["launches"] < occ["submits"] / 2, occ["histogram"]
    assert max(occ["histogram"]) > 1
    # a launch of single specs never outgrows the rung one spec pads to
    assert max(occ["fused_hist"]) <= 8
    assert acc.leader_active is False and acc.items == []
    # every slot came back
    assert acc.pipeline.acquire(blocking=False)
    assert acc.pipeline.acquire(blocking=False)
    assert not acc.pipeline.acquire(blocking=False)


def test_lone_submit_with_a_free_slot_is_launched_at_once(
    dindex, monkeypatch
):
    """No wait was added: with one launch held in its fetch and the
    second slot free, a lone arrival is popped and launched at once, as
    a batch of one, while the first launch is still out."""
    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    gate = threading.Event()
    entered = _hold_fetch(monkeypatch, gate=gate)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)
    got = [None, None]

    def one(i):
        got[i] = mb.submit(di, spec, window_cap=256, record_cap=64)

    first = threading.Thread(target=one, args=(0,))
    first.start()
    _wait_until(lambda: len(entered) == 1)
    second = threading.Thread(target=one, args=(1,))
    second.start()
    # the second launch reaches ITS fetch with the first still held
    _wait_until(lambda: len(entered) == 2)
    assert got == [None, None]
    assert mb.occupancy()["histogram"] == {1: 2}
    gate.set()
    for t in (first, second):
        t.join(30)
        assert not t.is_alive()
    ref = run_queries(di, [spec], window_cap=256, record_cap=64)
    for g in got:
        _answers_equal(g, ref)
    mb.close()


def _queue_behind_held_slots(acc, submit_one, n):
    """Both fetch slots taken by the test, then ``n`` submissions queued
    one after the other (so their order is known). Returns the threads;
    the caller gives the slots back."""
    assert acc.pipeline.acquire(blocking=False)
    assert acc.pipeline.acquire(blocking=False)
    threads = []
    for i in range(n):
        t = threading.Thread(target=submit_one, args=(i,))
        t.start()
        threads.append(t)
        _wait_until(lambda: len(acc.items) == i + 1)
    return threads


@pytest.mark.parametrize(
    "family, specs_each, per_launch",
    [
        # DeviceIndex: one spec pads to rung 8, so eight ride a launch
        ("xla", 1, 8),
        # fused entries of a whole rung go one a launch ...
        ("fused", 8, 1),
        # ... and entries of half a rung go two a launch
        ("fused", 4, 2),
        # an odd size still fills only the rung its head pays for
        ("fused", 3, 2),
        # the scattered kernel pads every tier to its 64-slot chunk
        ("scatter", 1, 64),
    ],
)
def test_a_launch_fills_only_the_shape_its_head_pays_for(
    dindex, family, specs_each, per_launch
):
    from sbeacon_tpu.ops import launch_capacity
    from sbeacon_tpu.ops.kernel import FusedDeviceIndex
    from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex

    shard, di = dindex
    if family == "fused":
        index = FusedDeviceIndex([shard, shard], pad_unit=1024)
    elif family == "scatter":
        index = ScatterDeviceIndex(shard)
    else:
        index = di
    assert launch_capacity(index, specs_each) // specs_each == per_launch
    n = 2 * per_launch + 1 if per_launch < 64 else 70
    pool = specs_for(shard, n * specs_each)
    mb = MicroBatcher(max_batch=512, max_wait_ms=0)
    acc = mb._accum(index, (256, 64))
    launched = []
    orig_execute = mb._execute

    def spying_execute(acc_, batch, *a):
        launched.append(list(batch))
        return orig_execute(acc_, batch, *a)

    mb._execute = spying_execute
    got = [None] * n

    def submit_one(i):
        specs = pool[i * specs_each : (i + 1) * specs_each]
        got[i] = mb.submit_many(
            index,
            specs,
            window_cap=256,
            record_cap=64,
            shard_ids=(
                [k % 2 for k in range(specs_each)]
                if family == "fused"
                else None
            ),
        )

    threads = _queue_behind_held_slots(acc, submit_one, n)
    with acc.lock:
        entries = list(acc.items)
        assert acc.leader_active
    acc.pipeline.release()
    acc.pipeline.release()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    mb.close()
    # first come first served, and each launch as full as its head's
    # padded shape allows, no fuller
    assert [p for batch in launched for p in batch] == entries
    sizes = [len(batch) for batch in launched]
    full, rest = divmod(n, per_launch)
    assert sizes == [per_launch] * full + ([rest] if rest else [])
    for i in range(n):
        specs = pool[i * specs_each : (i + 1) * specs_each]
        for k, spec in enumerate(specs):
            ref = run_queries(di, [spec], window_cap=256, record_cap=64)
            assert bool(got[i].exists[k]) == bool(ref.exists[0]), (i, k)
            assert int(got[i].call_count[k]) == int(ref.call_count[0])


def test_no_shape_is_launched_that_warmup_did_not_compile(dindex):
    """Under concurrency the batcher launches only (program, shape)
    keys that a launch of ONE entry of each size already compiled."""
    from sbeacon_tpu.ops.kernel import FusedDeviceIndex
    from sbeacon_tpu.telemetry import device_warmup_phase, flight_recorder

    shard, _di = dindex
    findex = FusedDeviceIndex([shard, shard], pad_unit=2048)
    sizes = (1, 3, 4, 8, 12, 16)
    pool = specs_for(shard, 64)
    mb = MicroBatcher(max_batch=512, max_wait_ms=0)

    def submit(k, off=0):
        return mb.submit_many(
            findex,
            pool[off : off + k],
            shard_ids=[j % 2 for j in range(k)],
            window_cap=256,
            record_cap=64,
        )

    with device_warmup_phase():
        for k in sizes:
            submit(k)

    def keys():
        return {
            e["key"]
            for e in flight_recorder.compile_snapshot()["entries"]
            if "FusedDeviceIndex:2048" in e["key"]
        }

    warmed = keys()
    assert warmed
    before = mb.occupancy()["launches"]

    def client(t):
        for j in range(12):
            submit(sizes[(t + j) % len(sizes)], off=(3 * t + j) % 40)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    mb.close()
    assert keys() == warmed
    assert mb.occupancy()["launches"] - before <= 12 * 12


def test_follower_behind_a_waiting_leader_times_out_and_withdraws(
    dindex, monkeypatch
):
    """A follower queued while the leader waits for a slot leaves at
    its own bound, with today's classification: 503 for the local
    timeout, 504 under a lapsed request deadline."""
    from sbeacon_tpu.resilience import (
        BatchTimeout,
        Deadline,
        DeadlineExceeded,
        deadline_scope,
    )

    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)
    acc = mb._accum(di, (256, 64))
    leader_got = []
    threads = _queue_behind_held_slots(
        acc,
        lambda i: leader_got.append(
            mb.submit(di, spec, window_cap=256, record_cap=64)
        ),
        1,
    )
    assert acc.leader_active  # parked on the slot, leadership claimed
    with pytest.raises(BatchTimeout):
        mb.submit(di, spec, window_cap=256, record_cap=64, timeout_s=0.15)
    with deadline_scope(Deadline.after(0.15)):
        with pytest.raises(DeadlineExceeded):
            mb.submit(di, spec, window_cap=256, record_cap=64)
    occ = mb.occupancy()
    assert (occ["timeouts"], occ["expired"], occ["launches"]) == (1, 1, 0)
    assert len(acc.items) == 1 and acc.leader_active  # both withdrew
    acc.pipeline.release()
    acc.pipeline.release()
    threads[0].join(30)
    assert not threads[0].is_alive()
    assert leader_got and leader_got[0].exists is not None
    assert mb.occupancy()["histogram"] == {1: 1}
    mb.close()


def test_leader_whose_slot_never_comes_returns_at_its_deadline(dindex):
    """The leader's wait for the slot is bounded by its own deadline:
    it withdraws, the leadership passes to a drainer that serves the
    follower once a slot frees, and no slot is lost."""
    from sbeacon_tpu.resilience import BatchTimeout

    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0)
    acc = mb._accum(di, (256, 64))
    raised = []

    def leader(_i):
        try:
            mb.submit(di, spec, window_cap=256, record_cap=64, timeout_s=0.2)
        except BaseException as e:  # noqa: BLE001 - recorded
            raised.append(e)

    threads = _queue_behind_held_slots(acc, leader, 1)
    follower_got = []
    ft = threading.Thread(
        target=lambda: follower_got.append(
            mb.submit(di, spec, window_cap=256, record_cap=64)
        )
    )
    ft.start()
    _wait_until(lambda: len(acc.items) == 2)
    threads[0].join(10)
    assert not threads[0].is_alive()
    assert len(raised) == 1 and isinstance(raised[0], BatchTimeout)
    # the follower is still queued and somebody still leads for it
    assert len(acc.items) == 1 and acc.leader_active
    assert mb.occupancy()["launches"] == 0
    acc.pipeline.release()
    acc.pipeline.release()
    ft.join(30)
    assert not ft.is_alive()
    assert follower_got and follower_got[0].exists is not None
    _wait_until(lambda: not acc.leader_active)
    mb.close()
    assert acc.items == []
    assert acc.pipeline.acquire(blocking=False)
    assert acc.pipeline.acquire(blocking=False)
    assert not acc.pipeline.acquire(blocking=False)


@pytest.mark.parametrize("path", ["empty", "all_expired", "failed_dispatch"])
def test_a_slot_taken_for_nothing_is_given_back(dindex, path):
    """The ways out that launch nothing: the queue emptied while the
    drainer waited, every popped entry had expired, the launcher
    refused the batch. Each gives its slot back."""
    import time

    from sbeacon_tpu.resilience import NO_DEADLINE, Deadline
    from sbeacon_tpu.serving import _Pending

    shard, di = dindex
    (spec,) = specs_for(shard, 1)
    mb = MicroBatcher(max_batch=64, max_wait_ms=0, default_timeout_s=5.0)
    acc = mb._accum(di, (256, 64))
    entry = _Pending(
        specs=[spec], event=threading.Event(), t_submit=time.perf_counter()
    )
    with acc.lock:
        acc.leader_active = True
        if path != "empty":
            acc.items.append(entry)
    if path == "all_expired":
        entry.deadline = entry.req_deadline = Deadline(time.monotonic() - 1)
        mb._serve(acc, di, 256, 64, None, NO_DEADLINE)
        assert entry.event.is_set() and entry.error is not None
    elif path == "failed_dispatch":
        mb.close()  # the launcher refuses every task from here on
        with pytest.raises(RuntimeError):
            mb._serve(acc, di, 256, 64, None, NO_DEADLINE)
        assert entry.event.is_set()
        assert isinstance(entry.error, RuntimeError)
    else:
        mb._serve(acc, di, 256, 64, None, NO_DEADLINE)
    assert mb.occupancy()["launches"] == 0
    assert acc.items == []
    if path != "failed_dispatch":
        assert acc.leader_active is False
    assert acc.pipeline.acquire(blocking=False)
    assert acc.pipeline.acquire(blocking=False)
    assert not acc.pipeline.acquire(blocking=False)
    mb.close()
