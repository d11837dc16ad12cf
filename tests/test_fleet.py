"""Fleet-wide observability (ISSUE 12): worker digest federation
(``/ops/digest`` -> FleetView -> ``/fleet/status``), the known-answer
canary prober (canary.py), /ops/events forward pagination, and the
/_trace trace-id index."""

import random

import pytest

from sbeacon_tpu.config import (
    BeaconConfig,
    EngineConfig,
    ObservabilityConfig,
    StorageConfig,
)
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.parallel.dispatch import (
    DistributedEngine,
    WorkerServer,
    ops_digest,
)
from sbeacon_tpu.parallel.transport import urllib_get
from sbeacon_tpu.telemetry import (
    EventJournal,
    RequestContext,
    journal,
    request_context,
)
from sbeacon_tpu.testing import random_records
from sbeacon_tpu.utils.trace import Tracer

obs = pytest.mark.obs

#: golden key set of the worker /ops/digest document
DIGEST_KEYS = {
    "time",
    "datasets",
    "datasetsTotal",
    "baseFingerprint",
    "datasetFingerprints",
    "deltaTails",
    "deltaPublishes",
    "openBreakers",
    "midRequestCompiles",
    "worstPadWaste",
}

#: golden key set of the /fleet/status document
FLEET_KEYS = {
    "intervalS",
    "polls",
    "lastPollAgeS",
    "workers",
    "diagnosis",
    "local",
}

DIAGNOSIS_KEYS = {
    "stalestReplica",
    "hottestWorker",
    "divergentDatasets",
    "unreachableWorkers",
    "worstCompilingReplica",
}


def _records(seed: int, n: int):
    return random_records(random.Random(seed), chrom="1", n=n, n_samples=2)


def _engine(ds, recs, *, delta_recs=None):
    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False))
    )
    eng.add_index(
        build_index(
            recs,
            dataset_id=ds,
            vcf_location=f"{ds}.vcf.gz",
            sample_names=["S0", "S1"],
        )
    )
    if delta_recs:
        eng.add_delta(
            build_index(
                delta_recs,
                dataset_id=ds,
                vcf_location=f"{ds}.vcf.gz",
                sample_names=["S0", "S1"],
            )
        )
    return eng


def _coordinator_app(tmp_path, worker_urls, local_engine):
    from sbeacon_tpu.api import BeaconApp

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "store"),
        engine=EngineConfig(microbatch=False),
        observability=ObservabilityConfig(slow_query_ms=-1.0),
    )
    cfg.storage.ensure()
    dist = DistributedEngine(
        worker_urls, local=local_engine, config=cfg
    )
    return BeaconApp(cfg, engine=dist), dist


# -- worker /ops/digest --------------------------------------------------------


@obs
def test_worker_ops_digest_golden_schema_over_http():
    recs = _records(10, 60)
    eng = _engine("dgA", recs[:50], delta_recs=recs[50:])
    worker = WorkerServer(eng).start_background()
    try:
        code, doc = urllib_get(worker.address + "/ops/digest", 5.0)
        assert code == 200
        assert set(doc) == DIGEST_KEYS
        assert doc["datasets"] == ["dgA"]
        assert doc["datasetsTotal"] == 1
        assert doc["deltaPublishes"] == 1
        assert doc["deltaTails"]["dgA"]["shards"] == 1
        assert doc["deltaTails"]["dgA"]["rows"] > 0
        # the base fingerprint is the stack-staleness identity, stable
        # across the standing delta (which rides the FULL fingerprints)
        assert doc["baseFingerprint"] == eng.base_fingerprint()
        assert doc["datasetFingerprints"] == eng.dataset_fingerprints()
    finally:
        worker.shutdown()


@obs
def test_worker_ops_digest_rides_token_boundary():
    eng = _engine("dgB", _records(11, 20))
    worker = WorkerServer(eng, token="sek").start_background()
    try:
        code, doc = urllib_get(worker.address + "/ops/digest", 5.0)
        assert code == 401
        code, doc = urllib_get(
            worker.address + "/ops/digest",
            5.0,
            {"Authorization": "Bearer sek"},
        )
        assert code == 200 and set(doc) == DIGEST_KEYS
    finally:
        worker.shutdown()


@obs
def test_ops_digest_builder_accepts_extras():
    eng = _engine("dgC", _records(12, 20))
    doc = ops_digest(eng, extras={"sloBreached": ["g_variants"]})
    assert set(doc) == DIGEST_KEYS | {"sloBreached"}
    assert doc["sloBreached"] == ["g_variants"]


# -- /fleet/status -------------------------------------------------------------


@obs
def test_fleet_status_single_host_schema():
    from sbeacon_tpu.api import BeaconApp

    app = BeaconApp()
    try:
        status, doc = app.handle("GET", "/fleet/status")
        assert status == 200
        assert set(doc) == FLEET_KEYS
        assert doc["workers"] == {}
        assert set(doc["diagnosis"]) == DIAGNOSIS_KEYS
        assert doc["diagnosis"]["stalestReplica"] is None
        # the coordinator's own digest always rides along, with the
        # app-tier extras (SLO breaches, slow queries, cost, canary)
        local = doc["local"]
        assert DIGEST_KEYS <= set(local)
        assert "sloBreached" in local and "canary" in local
    finally:
        app.close()


@obs
def test_fleet_status_names_stalest_replica_on_divergence(tmp_path):
    """Two workers advertising DIFFERENT copies of one dataset: the
    fleet diagnosis must name the divergent dataset and the stale
    replica (the copy losing the row-count freshness heuristic)."""
    recs = _records(20, 80)
    fresh = WorkerServer(_engine("dvA", recs)).start_background()
    stale = WorkerServer(_engine("dvA", recs[:50])).start_background()
    app = dist = None
    try:
        app, dist = _coordinator_app(
            tmp_path, [fresh.address, stale.address], _engine("dvA", recs)
        )
        status, doc = app.handle("GET", "/fleet/status")
        assert status == 200
        workers = doc["workers"]
        assert set(workers) == {fresh.address, stale.address}
        assert all(w["reachable"] for w in workers.values())
        diag = doc["diagnosis"]
        assert "dvA" in diag["divergentDatasets"]
        assert set(diag["divergentDatasets"]["dvA"]) == {
            fresh.address, stale.address,
        }
        assert diag["stalestReplica"] == stale.address
        assert workers[stale.address]["staleDatasets"] == 1
        assert diag["unreachableWorkers"] == []
        # fleet.* series feed off the same cached state
        _, metrics = app.handle("GET", "/metrics")
        assert metrics["fleet"]["digest_polls"] >= 1
        assert metrics["fleet"]["workers_reachable"] == 2
        assert metrics["fleet"]["divergent_datasets"] == 1
    finally:
        if app is not None:
            app.close()
        if dist is not None:
            dist.close()
        fresh.shutdown()
        stale.shutdown()


@obs
def test_fleet_status_reports_unreachable_worker(tmp_path):
    recs = _records(21, 40)
    w1 = WorkerServer(_engine("unA", recs)).start_background()
    w2 = WorkerServer(_engine("unA", recs)).start_background()
    app = dist = None
    try:
        app, dist = _coordinator_app(
            tmp_path, [w1.address, w2.address], _engine("unA", recs)
        )
        _, doc = app.handle("GET", "/fleet/status")
        assert doc["diagnosis"]["unreachableWorkers"] == []
        w2.shutdown()
        dist.fleet.poll()  # explicit pass (the lazy cadence would wait)
        _, doc = app.handle("GET", "/fleet/status")
        assert doc["diagnosis"]["unreachableWorkers"] == [w2.address]
        assert not doc["workers"][w2.address]["reachable"]
        assert "error" in doc["workers"][w2.address]
    finally:
        if app is not None:
            app.close()
        if dist is not None:
            dist.close()
        w1.shutdown()
        w2.shutdown()


# -- the canary prober ---------------------------------------------------------


@obs
def test_canary_healthy_round_registers_and_passes(tmp_path):
    """On a healthy single-host engine the canary derives one hit and
    one miss probe per dataset and every probe passes — and zero
    canary traffic lands in SLO budgets or the cost table."""
    from sbeacon_tpu.api import BeaconApp

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "store"),
        engine=EngineConfig(microbatch=False),
    )
    cfg.storage.ensure()
    app = BeaconApp(cfg, engine=_engine("cnA", _records(30, 60)))
    try:
        assert app.canary.sync_probes() == 2
        out = app.canary.run_once()
        assert out["probes"] > 0
        assert out["mismatches"] == 0 and out["failures"] == 0
        _, doc = app.handle("GET", "/debug/status")
        assert doc["canary"]["registeredProbes"] == 2
        assert doc["canary"]["runs"] == 1
        assert doc["diagnosis"]["canaryMismatches"] == []
        # probe exclusion: no 'canary' route in SLO, no canary shape
        # in the cost table
        _, slo_doc = app.handle("GET", "/slo")
        assert "canary" not in slo_doc["routes"]
        _, costs = app.handle("GET", "/ops/costs")
        assert not any(
            k.startswith("canary") for k in costs["shapes"]
        )
        assert "canary" not in costs["tenants"]
    finally:
        app.close()


@obs
def test_canary_reregisters_probes_after_publish(tmp_path):
    from sbeacon_tpu.api import BeaconApp

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "store"),
        engine=EngineConfig(microbatch=False),
    )
    cfg.storage.ensure()
    recs = _records(31, 60)
    eng = _engine("cnB", recs[:50])
    app = BeaconApp(cfg, engine=eng)
    try:
        assert app.canary.sync_probes() == 2
        hit0 = next(
            p for p in app.canary._probes if p.kind == "hit"
        )
        eng.add_delta(
            build_index(
                recs[50:],
                dataset_id="cnB",
                vcf_location="cnB.vcf.gz",
                sample_names=["S0", "S1"],
            )
        )
        # the fingerprint changed, so the next sync re-derives — and
        # the hit probe now targets the delta (the newest publish)
        assert app.canary.sync_probes() == 2
        hit1 = next(
            p for p in app.canary._probes if p.kind == "hit"
        )
        assert hit1.payload != hit0.payload
        assert app.canary.run_once()["mismatches"] == 0
    finally:
        app.close()


@obs
def test_canary_detects_seeded_stale_replica(tmp_path):
    """The acceptance scenario: a replica whose delta tail is silently
    lost (the routed planes still trust it — its advertised identity
    was captured at discovery) fails the known-hit probe on the very
    next round, visible as a canary.mismatch journal event, canary.*
    metrics, and a /debug/status diagnosis entry."""
    recs = _records(32, 80)
    base, tail = recs[:60], recs[60:]
    w_ok = WorkerServer(
        _engine("cnC", base, delta_recs=tail)
    ).start_background()
    stale_engine = _engine("cnC", base, delta_recs=tail)
    w_bad = WorkerServer(stale_engine).start_background()
    app = dist = None
    try:
        app, dist = _coordinator_app(
            tmp_path,
            [w_ok.address, w_bad.address],
            _engine("cnC", base, delta_recs=tail),
        )
        dist.replica_table()  # both copies identical -> both routed
        assert app.canary.sync_probes() == 2
        assert app.canary.run_once()["mismatches"] == 0
        # probe RTTs must NOT feed the router's rings: sub-ms canary
        # probes would drag the adaptive hedge p95 to probe scale
        assert not dist.router._rtts
        # seed the fault: drop the replica's delta tail in place (its
        # answers change, nothing else announces it)
        with stale_engine._mesh_lock:
            stale_engine._deltas = {}
            stale_engine._rebuild_serving_state_locked()
        seq0 = journal.last_seq()
        out = app.canary.run_once()
        # the hit probe fails against the stale replica for BOTH query
        # shapes; every other path still passes
        assert out["mismatches"] == 2
        assert all(
            f"replica:{w_bad.address}" in m for m in out["mismatched"]
        )
        events = journal.events(since=seq0, kind="canary.mismatch")
        assert events, "no canary.mismatch flight-recorder event"
        assert events[0]["data"]["dataset"] == "cnC"
        assert events[0]["data"]["path"] == f"replica:{w_bad.address}"
        _, doc = app.handle("GET", "/debug/status")
        assert doc["canary"]["mismatches"] == 2
        assert doc["diagnosis"]["canaryMismatches"]
        _, metrics = app.handle("GET", "/metrics")
        assert metrics["canary"]["mismatches"] == 2
        assert metrics["canary"]["probes"] > 0
    finally:
        if app is not None:
            app.close()
        if dist is not None:
            dist.close()
        w_ok.shutdown()
        w_bad.shutdown()


@obs
def test_canary_symbolic_only_dataset_gets_miss_probe_only():
    """A dataset whose every row is a symbolic alt (<CN2>, <DEL>)
    cannot carry an exact-alt hit probe — registering one would be a
    permanent false alarm. It gets the known-miss probe only, and a
    healthy round stays clean."""
    from sbeacon_tpu.canary import CanaryProber

    recs = random_records(
        random.Random(40), chrom="1", n=30, n_samples=2, p_symbolic=1.0
    )
    assert all(a.startswith("<") for r in recs for a in r.alts)
    eng = _engine("svOnly", recs)
    bracket = eng.canary_brackets()["svOnly"]
    assert "pos" not in bracket and "alt" not in bracket
    prober = CanaryProber(eng, enabled=False)
    assert prober.sync_probes() == 1
    assert prober._probes[0].kind == "miss"
    out = prober.run_once()
    assert out["mismatches"] == 0 and out["failures"] == 0


@obs
def test_canary_hit_probe_skips_a_row_nobody_carries():
    """A plain row with an allele count of 0 answers exists=False on
    every path: anchoring the known-hit probe on it is a standing false
    alarm (the chip smoke met one: the newest delta's first row was
    monomorphic). The bracket takes the first plain row that is called."""
    from sbeacon_tpu.canary import CanaryProber

    recs = random_records(random.Random(43), chrom="1", n=40, n_samples=2)
    recs.sort(key=lambda r: r.pos)
    recs[0].alts, recs[0].ac = ["A" if recs[0].ref != "A" else "C"], [0]
    recs[0].genotypes = ["0|0", "0|0"]
    eng = _engine("mono", recs)
    shard = eng.index_snapshot()[0][1]
    assert int(shard.cols["ac"][0]) == 0
    bracket = eng.canary_brackets()["mono"]
    assert bracket["pos"] > int(shard.cols["pos"][0])
    prober = CanaryProber(eng, enabled=False)
    assert prober.sync_probes() == 2
    out = prober.run_once()
    assert out["mismatches"] == 0 and out["failures"] == 0


@obs
def test_canary_symbolic_delta_falls_back_to_base_hit_row():
    """A symbolic-only DELTA on top of a plain base must not drop the
    hit probe: the bracket walks shards newest-first and anchors on
    the freshest shard that has a plain-allele row (here the base), so
    staleness coverage survives an SV-only publish."""
    base = random_records(random.Random(41), chrom="1", n=40, n_samples=2)
    sv = random_records(
        random.Random(42),
        chrom="1",
        n=10,
        n_samples=2,
        start=5000,
        p_symbolic=1.0,
    )
    eng = _engine("svDelta", base, delta_recs=sv)
    bracket = eng.canary_brackets()["svDelta"]
    assert "pos" in bracket and "alt" in bracket
    assert not bracket["alt"].startswith("<")
    assert bracket["source"] == "svDelta.vcf.gz"  # the base anchored it
    from sbeacon_tpu.canary import CanaryProber

    prober = CanaryProber(eng, enabled=False)
    assert prober.sync_probes() == 2
    out = prober.run_once()
    assert out["mismatches"] == 0 and out["failures"] == 0


@obs
def test_probe_flag_stays_off_the_wire_and_unknown_keys_drop():
    """Rolling-deploy wire compat for the new payload field: a
    default-False no_response_cache never rides /search bodies (an
    old worker's constructor would reject it), and from_doc drops
    keys this build does not know (the forward half)."""
    import json

    from sbeacon_tpu.payloads import VariantQueryPayload

    plain = VariantQueryPayload(dataset_ids=["d"], reference_name="1")
    assert "no_response_cache" not in json.loads(plain.dumps())
    probe = VariantQueryPayload(
        dataset_ids=["d"], reference_name="1", no_response_cache=True
    )
    assert json.loads(probe.dumps())["no_response_cache"] is True
    # round-trips both ways, and future fields are dropped not fatal
    doc = json.loads(probe.dumps())
    doc["some_future_field"] = {"x": 1}
    got = VariantQueryPayload.from_doc(doc)
    assert got.no_response_cache is True
    assert VariantQueryPayload.loads(plain.dumps()) == plain


# -- /ops/events forward pagination --------------------------------------------


@obs
def test_events_page_tails_without_gaps_or_rereads():
    j = EventJournal(keep=64)
    for i in range(10):
        j.publish("pg.tick", i=i)
    seen, since, pages = [], 0, 0
    while True:
        page, nxt = j.events_page(since=since, limit=3)
        if not page:
            assert nxt == max(since, j.last_seq())
            break
        seen.extend(e["seq"] for e in page)
        assert nxt >= page[-1]["seq"]
        since = nxt
        pages += 1
    assert seen == list(range(1, 11))  # no gaps, no duplicates
    assert pages == 4  # 3+3+3+1


@obs
def test_events_page_kind_filter_skips_nonmatching():
    j = EventJournal(keep=64)
    j.publish("a.one")
    j.publish("b.two")
    j.publish("a.three")
    page, nxt = j.events_page(since=0, kind="a", limit=10)
    assert [e["kind"] for e in page] == ["a.one", "a.three"]
    # caught up: the cursor jumps PAST the non-matching tail so the
    # next poll does not rescan it
    assert nxt == j.last_seq()
    page, nxt2 = j.events_page(since=nxt, kind="a", limit=10)
    assert page == [] and nxt2 == nxt


@obs
def test_events_page_truncation_cursor_resumes_mid_burst():
    j = EventJournal(keep=64)
    for i in range(7):
        j.publish("burst.k", i=i)
    page, nxt = j.events_page(since=0, limit=5)
    assert [e["seq"] for e in page] == [1, 2, 3, 4, 5]
    assert nxt == 5  # truncated: resume right after the page
    page, nxt = j.events_page(since=nxt, limit=5)
    assert [e["seq"] for e in page] == [6, 7]
    assert nxt == 7
    assert j.events_page(since=0, limit=0) == ([], 0)


# -- /_trace trace-id index ----------------------------------------------------


@obs
def test_tracer_indexes_recent_trees_by_trace_id():
    t = Tracer(enabled=True, keep_trees=4)
    for i in range(6):
        with request_context(RequestContext(trace_id=f"tid{i}")):
            with t.span("root", i=i):
                with t.span("child"):
                    pass
    # O(1) lookup through the index, newest retained
    got = t.recent_trees(trace_id="tid5")
    assert len(got) == 1 and got[0]["meta"]["i"] == 5
    assert got[0]["children"][0]["name"] == "child"
    # evicted trees leave the index too (no unbounded growth, no
    # stale hits)
    assert t.recent_trees(trace_id="tid0") == []
    assert set(t._by_trace) == {"tid2", "tid3", "tid4", "tid5"}
    # unfiltered view unchanged
    assert len(t.recent_trees()) == 4
    t.reset()
    assert t._by_trace == {}
