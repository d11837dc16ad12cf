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
    # the local engine's programs plus the pod mesh tier's own batch
    # tiers (when >=2 devices are visible the tier warms too)
    assert dist.warmup() >= n
    dist.close()
    eng.close()
