"""Performance-contract smoke tests (perf_smoke marker, tier-1 fast).

These assert the two launch-count invariants the fused-dispatch /
response-cache overhaul exists to provide, on the CPU backend in
seconds: a k-shard query is ONE kernel launch (not k), and a warm
cache hit is ZERO launches. They are contracts, not benchmarks: a
time is a chip run of ``benchmark/run.py``.
"""

import random

import pytest

import sbeacon_tpu.ops.kernel as kernel_mod
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records

N_SHARDS = 4


def _engine(**eng_over):
    eng_over.setdefault("use_mesh", False)
    cfg = BeaconConfig(
        engine=EngineConfig(microbatch_wait_ms=0.0, **eng_over)
    )
    eng = VariantEngine(cfg)
    shards = []
    for d in range(N_SHARDS):
        rng = random.Random(40 + d)
        recs = random_records(rng, chrom="1", n=250, n_samples=2)
        s = build_index(
            recs,
            dataset_id=f"d{d}",
            vcf_location=f"v{d}",
            sample_names=["S0", "S1"],
        )
        shards.append(s)
        eng.add_index(s)
    return eng, shards


def _payload():
    return VariantQueryPayload(
        dataset_ids=[f"d{d}" for d in range(N_SHARDS)],
        reference_name="1",
        start_min=1,
        start_max=1 << 29,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity="count",
        include_datasets="HIT",
    )


def _launches() -> int:
    # every kernel family counts: XLA gather (CPU tier-1), scatter
    # tiles, and the engine's mesh program
    from sbeacon_tpu.ops import scatter_kernel
    from sbeacon_tpu.parallel import mesh as mesh_mod

    return (
        kernel_mod.N_LAUNCHES
        + scatter_kernel.N_DISPATCHES
        + mesh_mod.N_LAUNCHES
    )


@pytest.mark.perf_smoke
def test_multi_shard_query_is_one_fused_launch():
    """A 4-shard query must issue exactly ONE device launch through the
    fused stacked index (pre-overhaul: one per shard), with per-dataset
    responses intact."""
    eng, shards = _engine()
    try:
        eng.warmup()  # compiles outside the measured window
        n0 = _launches()
        responses = eng.search(_payload())
        n1 = _launches()
        assert n1 - n0 == 1, f"expected 1 fused launch, saw {n1 - n0}"
        assert eng.fused_searches == 1
        assert [r.dataset_id for r in responses] == [
            f"d{d}" for d in range(N_SHARDS)
        ]
        assert all(r.exists for r in responses)
    finally:
        eng.close()


@pytest.mark.perf_smoke
def test_warm_cache_hit_is_zero_launches():
    """A repeated query must be served from the response cache without
    touching the device at all."""
    eng, _shards = _engine()
    try:
        eng.warmup()
        first = eng.search(_payload())
        n0 = _launches()
        again = eng.search(_payload())
        n1 = _launches()
        assert n1 - n0 == 0, f"cache hit dispatched {n1 - n0} launches"
        stats = eng.cache_stats()
        assert stats is not None and stats["hits"] >= 1
        assert [(r.dataset_id, r.call_count, r.exists) for r in first] == [
            (r.dataset_id, r.call_count, r.exists) for r in again
        ]
    finally:
        eng.close()


@pytest.mark.perf_smoke
@pytest.mark.ingest
def test_delta_publish_keeps_warm_cache_and_fused_stack():
    """Ingest-while-serving (ISSUE 10): a delta publish must NOT reset
    the warm query plane — a cached query whose region/dataset does not
    overlap the new rows still answers with ZERO launches, and the
    fused stack stays clean (no rebuild, next cold query is still one
    fused launch)."""
    from sbeacon_tpu.genomics.vcf import VcfRecord

    eng, _shards = _engine()
    try:
        eng.warmup()
        first = eng.search(_payload())  # cached (chr1 bracket, d0-d3)
        delta = build_index(
            [
                VcfRecord(
                    chrom="2",
                    pos=777,
                    ref="A",
                    alts=["T"],
                    ac=[1],
                    an=4,
                    vt="SNP",
                    genotypes=["0|1", "0|0"],
                )
            ],
            dataset_id="d0",
            vcf_location="v0",
            sample_names=["S0", "S1"],
        )
        eng.add_delta(delta)  # chr2: disjoint from the cached bracket
        assert eng._fused_dirty is False, (
            "delta publish dirtied the fused stack"
        )
        n0 = _launches()
        again = eng.search(_payload())
        assert _launches() - n0 == 0, (
            "delta publish dropped a non-overlapping cache entry"
        )
        assert [(r.dataset_id, r.call_count) for r in first] == [
            (r.dataset_id, r.call_count) for r in again
        ]
        assert eng.cache_stats()["scoped_invalidations"] >= 1
    finally:
        eng.close()


# -- coordinator-worker data plane (ISSUE 5) ----------------------------------


def _worker_payload(granularity="boolean", include="NONE", datasets=()):
    return VariantQueryPayload(
        dataset_ids=list(datasets),
        reference_name="1",
        start_min=1,
        start_max=1 << 30,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity=granularity,
        include_datasets=include,
    )


@pytest.mark.perf_smoke
def test_sequential_worker_calls_bounded_by_pool_size():
    """N sequential coordinator->worker calls must ride pooled
    keep-alive connections: the worker accepts at most pool_size TCP
    connections, not one per call (the pre-ISSUE-5 behavior)."""
    from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer
    from sbeacon_tpu.parallel.transport import PooledTransport

    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False, use_mesh=False))
    )
    rng = random.Random(77)
    eng.add_index(
        build_index(
            random_records(rng, chrom="1", n=120, n_samples=2),
            dataset_id="dsP",
            vcf_location="p.vcf.gz",
            sample_names=["S0", "S1"],
        )
    )
    w = WorkerServer(eng).start_background()
    accepts = [0]
    orig = w.server.get_request

    def counting_get_request():
        accepts[0] += 1
        return orig()

    w.server.get_request = counting_get_request
    transport = PooledTransport(pool_size=2)
    dist = DistributedEngine([w.address], transport=transport)
    n_calls = 6
    try:
        for _ in range(n_calls):
            got = dist.search(_worker_payload(datasets=["dsP"]))
            assert got and got[0].exists
        # discovery GET + 6 searches all rode pooled connections
        assert accepts[0] <= transport.pool_size, accepts
        assert accepts[0] < n_calls
        assert transport.metrics()["reused"] >= n_calls - 1
    finally:
        dist.close()
        w.shutdown()
        eng.close()


@pytest.mark.perf_smoke
def test_boolean_short_circuit_over_three_workers():
    """A boolean-granularity fan-out over >=3 workers returns as soon
    as any worker reports a hit — the slow siblings are abandoned and
    dispatch.short_circuits increments."""
    import time

    from sbeacon_tpu.parallel.dispatch import DistributedEngine

    slow_s = 0.6
    urls = ["http://wslow1:1", "http://wslow2:1", "http://whit:1"]

    def post(url, doc, timeout_s, headers=None):
        base = url.rsplit("/", 1)[0]  # strip /search
        if "whit" in url:
            return 200, {
                "responses": [
                    {
                        "dataset_id": f"ds::{base}",
                        "vcf_location": "v",
                        "exists": True,
                    }
                ]
            }
        time.sleep(slow_s)
        return 200, {"responses": [
            {"dataset_id": f"ds::{base}", "vcf_location": "v",
             "exists": False}
        ]}

    def get(url, timeout_s, headers=None):
        base = url.rsplit("/", 1)[0]  # strip /datasets
        return 200, {"datasets": [f"ds::{base}"], "fingerprint": base}

    dist = DistributedEngine(urls, retries=0, post=post, get=get)
    try:
        t0 = time.perf_counter()
        got = dist.search(
            _worker_payload(datasets=[f"ds::{u}" for u in urls])
        )
        took = time.perf_counter() - t0
        assert any(r.exists for r in got)
        assert took < slow_s * 0.8, took  # did NOT wait for the drain
        assert dist.short_circuits == 1
    finally:
        dist.close()


@pytest.mark.perf_smoke
def test_hedged_scan_not_gated_by_slow_worker():
    """A seeded-slow worker must not gate scan_blob completion: after
    the hedge delay the scan races a second worker and the first
    response wins."""
    import time

    from sbeacon_tpu.parallel.dispatch import ScanWorkerPool
    from sbeacon_tpu.payloads import SliceScanPayload

    slow_s = 0.8

    def post_bytes(url, doc, timeout_s, headers=None):
        if "slow" in url:
            time.sleep(slow_s)
            return 200, b"blob-slow"
        return 200, b"blob-fast"

    pool = ScanWorkerPool(
        ["http://slow:1", "http://fast:1"],
        retries=0,
        hedge_delay_s=0.05,
        post_bytes=post_bytes,
    )
    try:
        t0 = time.perf_counter()
        blob = pool.scan_blob(SliceScanPayload(dataset_id="d"))
        took = time.perf_counter() - t0
        assert blob == b"blob-fast"
        assert took < slow_s * 0.8, took
        stats = pool.stats()
        assert stats["hedges"] == 1 and stats["hedge_wins"] == 1
    finally:
        pool.close()


# -- a coordinator's local leg (ISSUE 47) -------------------------------------


def _worker_beside(eng):
    """A live worker in the fleet (so "zero HTTP" is the local leg's
    doing, not an empty topology) and the coordinator over both."""
    from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer

    weng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False, use_mesh=False))
    )
    weng.add_index(
        build_index(
            random_records(random.Random(9), chrom="1", n=120, n_samples=2),
            dataset_id="wrk",
            vcf_location="wrk.vcf.gz",
            sample_names=["S0", "S1"],
        )
    )
    worker = WorkerServer(weng).start_background()
    return weng, worker, DistributedEngine([worker.address], local=eng)


def _transport_snapshot() -> dict:
    from sbeacon_tpu.parallel import transport as transport_mod

    keys = ("opened", "reused", "evicted", "retried", "gzip_bodies")
    return {k: transport_mod._STATS.get(k) for k in keys}


@pytest.mark.perf_smoke
def test_coordinator_boolean_query_is_one_launch_zero_http():
    """A 4-dataset boolean query over a coordinator's LOCAL datasets
    must cost exactly ONE kernel launch (the engine's own mesh program)
    and ZERO coordinator->worker HTTP calls (the pooled transport's
    process-wide stats unchanged across the query) — the reference
    shape was k Lambda RTTs plus a DynamoDB counter poll."""
    import jax

    from sbeacon_tpu.telemetry import flight_recorder

    if len(jax.devices()) < 2:
        pytest.skip("the mesh stack needs >=2 devices (forced-host CI mesh)")
    eng, _shards = _engine(use_mesh=True)
    weng, worker, dist = _worker_beside(eng)
    try:
        dist.replica_table()  # discovery rides HTTP, OUTSIDE the probe
        dist.warmup()  # compiles outside the measured window
        t0 = _transport_snapshot()
        n0 = _launches()
        m0 = flight_recorder.launches_by_family().get("mesh", 0)
        got = dist.search(
            _worker_payload(datasets=[f"d{d}" for d in range(N_SHARDS)])
        )
        assert _launches() - n0 == 1, "expected exactly one mesh launch"
        assert flight_recorder.launches_by_family().get("mesh", 0) == m0 + 1
        assert _transport_snapshot() == t0, "local query touched the transport"
        assert any(r.exists for r in got)
        assert eng.mesh_searches == 1
    finally:
        dist.close()
        worker.shutdown()
        weng.close()
        eng.close()


@pytest.mark.perf_smoke
def test_coordinator_selected_query_is_one_launch_an_owner_zero_http(
    monkeypatch,
):
    """A selected-samples query over a coordinator's local datasets is
    one match+planes launch an OWNER chip and nothing else (the chip's
    index family forced on the CPU, where every dataset has a chip of
    its own), with ZERO coordinator->worker HTTP calls, byte-identical
    to the per-dataset path."""
    import dataclasses

    import jax

    import sbeacon_tpu.engine as engine_mod
    import sbeacon_tpu.telemetry as tel
    from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex

    if len(jax.devices()) < 2:
        pytest.skip("several owner chips need >=2 devices")
    ref_eng, _ = _engine(microbatch=False)
    monkeypatch.setattr(
        engine_mod,
        "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    rec = tel.DeviceFlightRecorder()
    monkeypatch.setattr(tel, "flight_recorder", rec)
    eng, _shards = _engine(use_mesh=True)
    weng, worker, dist = _worker_beside(eng)
    datasets = [f"d{d}" for d in range(N_SHARDS)]
    pay = dataclasses.replace(
        _worker_payload(granularity="record", include="ALL",
                        datasets=datasets),
        selected_samples_only=True,
        sample_names={d: ["S1"] for d in datasets},
    )
    try:
        dist.replica_table()  # discovery rides HTTP, OUTSIDE the probe
        dist.warmup()  # compiles outside the measured window
        ref = ref_eng.search(pay)
        owners = {row["chip"] for row in eng.placement_table()}
        t0 = _transport_snapshot()
        f0 = rec.launches_by_family()
        got = dist.search(pay)
        f1 = rec.launches_by_family()
        assert {
            f: f1[f] - f0.get(f, 0) for f in f1 if f1[f] != f0.get(f, 0)
        } == {"plane": len(owners)}
        assert len(owners) > 1
        assert _transport_snapshot() == t0, "plane query touched the transport"
        assert rec.fallbacks_by_site() == {}
        assert [dataclasses.asdict(r) for r in got] == [
            dataclasses.asdict(r) for r in ref
        ]
    finally:
        dist.close()
        worker.shutdown()
        weng.close()
        ref_eng.close()
        eng.close()


# -- observability stays off the hot path (ISSUE 7) ---------------------------


@pytest.mark.perf_smoke
def test_observability_keeps_warm_path_contract():
    """With the FULL observability surface armed — latency exemplars,
    SLO burn-rate tracking, flight recorder, slow-query compare — a
    warm repeated query through the API stays inside the existing
    contract: ZERO device launches and millisecond-scale handling. The
    instruments must explain the hot path, never tax it."""
    import time

    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.telemetry import journal

    eng, _shards = _engine()
    app = BeaconApp(engine=eng)
    try:
        assert journal.enabled  # flight recorder armed (default-on)
        app.store.upsert(
            "datasets",
            [
                {
                    "id": f"d{d}",
                    "name": f"d{d}",
                    "_assemblyId": "GRCh38",
                    "_vcfLocations": [f"v{d}"],
                }
                for d in range(N_SHARDS)
            ],
        )
        eng.warmup()
        body = {
            "query": {
                "requestedGranularity": "boolean",
                "requestParameters": {
                    "assemblyId": "GRCh38",
                    "referenceName": "1",
                    "start": [1],
                    "end": [1 << 29],
                    "alternateBases": "N",
                },
            }
        }
        status, first = app.handle("POST", "/g_variants", body=body)
        assert status == 200  # prime the response/job caches
        n0 = _launches()
        times = []
        for _ in range(100):
            t0 = time.perf_counter()
            status, out = app.handle("POST", "/g_variants", body=body)
            times.append(time.perf_counter() - t0)
            assert status == 200
        assert _launches() - n0 == 0, "warm repeats touched the device"
        times.sort()
        p50_ms = times[len(times) // 2] * 1e3
        # generous CI bound; the real number is sub-millisecond — the
        # contract is "observability did not add a visible tax", not a
        # benchmark claim (those are chip runs of benchmark/run.py)
        assert p50_ms < 25.0, f"warm handle p50 {p50_ms:.2f} ms"
        # the surfaces actually engaged: exemplars recorded, SLO
        # counted the traffic
        _, metrics = app.handle("GET", "/metrics")
        assert "exemplars" in metrics["request"]["latency_ms"]["g_variants"]
        _, slo = app.handle("GET", "/slo")
        win = slo["routes"]["g_variants"]["availability"]["windows"]["5m"]
        assert win["good"] >= 100 and win["burnRate"] == 0.0
    finally:
        app.close()
        eng.close()


@pytest.mark.perf_smoke
def test_cache_disabled_still_fuses():
    """response_cache=False keeps the fused single-launch contract and
    re-executes repeats (no stale shortcuts)."""
    eng, _shards = _engine(response_cache=False)
    try:
        eng.warmup()
        assert eng.cache_stats() is None
        n0 = _launches()
        eng.search(_payload())
        eng.search(_payload())
        n1 = _launches()
        assert n1 - n0 == 2
    finally:
        eng.close()
