"""SLO burn-rate engine, histogram exemplars, flight recorder, and the
/debug/status rollup (ISSUE 7): burn math under an injectable clock,
GOLDEN-style schema stability for /slo, /ops/events and /debug/status,
OpenMetrics exemplar syntax validity, and the acceptance integration —
a worker kill -> failover -> rediscovery heal leaves a matching event
sequence in /ops/events, a burn-rate rise on the affected route at
/slo, and an exemplar whose trace id resolves at /_trace."""

import json
import random
import re
import time

import pytest

from sbeacon_tpu.config import (
    BeaconConfig,
    EngineConfig,
    ObservabilityConfig,
    ResilienceConfig,
    StorageConfig,
)
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.slo import (
    SloEngine,
    SloObjective,
    parse_route_objectives,
)
from sbeacon_tpu.telemetry import (
    EventJournal,
    Histogram,
    MetricsRegistry,
    RequestContext,
    journal,
    new_trace_id,
    publish_event,
    request_context,
)
from sbeacon_tpu.testing import random_records

obs = pytest.mark.obs


# -- SLO engine unit (injectable clock) ----------------------------------------


def _engine_at(clk, **kw):
    return SloEngine(clock=lambda: clk[0], **kw)


@obs
def test_availability_burn_rate_math():
    clk = [0.0]
    eng = _engine_at(clk, default=SloObjective(availability_target=0.999))
    for _ in range(99):
        eng.record("g_variants", 200, 1.0)
    eng.record("g_variants", 503, 1.0)
    # bad ratio 1% against a 0.1% budget: burn 10x on both windows
    rates = eng.burn_rates("availability")
    assert rates[("g_variants", "5m")] == pytest.approx(10.0, rel=0.01)
    assert rates[("g_variants", "1h")] == pytest.approx(10.0, rel=0.01)
    # zero-traffic routes don't exist; excluded routes never track
    eng.record("metrics", 500, 1.0)
    eng.record("ops.events", 500, 1.0)
    assert set(eng.snapshot()["routes"]) == {"g_variants"}


@obs
def test_latency_burn_counts_only_successes():
    clk = [0.0]
    eng = _engine_at(
        clk,
        default=SloObjective(latency_ms=50.0, latency_target=0.9),
    )
    for _ in range(8):
        eng.record("boolean", 200, 10.0)
    for _ in range(2):
        eng.record("boolean", 200, 500.0)  # over threshold
    eng.record("boolean", 500, 9999.0)  # 5xx: availability, not latency
    win = eng.snapshot()["routes"]["boolean"]["latency"]["windows"]["5m"]
    assert win["total"] == 10 and win["bad"] == 2
    # 20% slow against a 10% budget: burn 2x
    assert win["burnRate"] == pytest.approx(2.0, rel=0.01)


@obs
def test_windows_age_out_independently():
    clk = [0.0]
    eng = _engine_at(clk)
    for _ in range(10):
        eng.record("info", 500, 1.0)
    r = eng.burn_rates("availability")
    assert r[("info", "5m")] > 0 and r[("info", "1h")] > 0
    clk[0] = 400.0  # past the 5m window, inside the 1h one
    r = eng.burn_rates("availability")
    assert r[("info", "5m")] == 0.0 and r[("info", "1h")] > 0
    clk[0] = 4000.0  # past both
    r = eng.burn_rates("availability")
    assert r[("info", "1h")] == 0.0


@obs
def test_breached_requires_both_windows_over_alert_factor():
    clk = [0.0]
    eng = _engine_at(clk, alert_burn_rate=5.0)
    for _ in range(9):
        eng.record("g_variants", 200, 1.0)
    eng.record("g_variants", 500, 1.0)  # 10% vs 0.1% budget: burn 100x
    # ... but ONE bad event is not a trend, whatever it burns
    assert eng.breached() == {"g_variants": 0}
    eng.record("g_variants", 500, 1.0)
    assert eng.breached() == {"g_variants": 1}
    assert eng.breached_routes() == ["g_variants"]
    # an hour later the fast window is clean: no longer breached (the
    # two-window AND is the whole point — stale burn alone can't page)
    clk[0] = 3000.0
    assert eng.breached() == {"g_variants": 0}


@obs
def test_route_objective_parsing_and_env():
    default = SloObjective()
    parsed = parse_route_objectives(
        "g_variants:latency_ms=50:latency_target=0.99, info:availability=0.99",
        default,
    )
    assert parsed["g_variants"].latency_ms == 50.0
    assert parsed["g_variants"].availability_target == 0.999
    assert parsed["info"].availability_target == 0.99
    with pytest.raises(ValueError):
        parse_route_objectives("g_variants:bogus=1", default)
    with pytest.raises(ValueError):
        parse_route_objectives(":latency_ms=1", default)
    # config-tier construction (the BEACON_SLO_* surface)
    obs_cfg = ObservabilityConfig(
        slo_latency_ms=75.0, slo_routes="boolean:latency_ms=50"
    )
    eng = SloEngine.from_config(obs_cfg)
    assert eng.default.latency_ms == 75.0
    assert eng.overrides["boolean"].latency_ms == 50.0
    # declared routes surface at /slo even before any traffic
    assert "boolean" in eng.snapshot()["routes"]


@obs
def test_submit_is_a_batch_route_with_no_default_latency_threshold():
    """Multi-second cohort ingests never burn a latency budget (and so
    never brown out the queries beside them) unless the operator
    declares a threshold for the route; 5xx still counts."""
    eng = SloEngine.from_config(ObservabilityConfig())
    for _ in range(5):
        eng.record("submit", 200, 22_000.0)
    doc = eng.snapshot()["routes"]["submit"]
    assert doc["latency"]["thresholdMs"] is None
    assert doc["latency"]["windows"]["5m"]["bad"] == 0
    assert eng.breached_routes() == []
    json.dumps(eng.snapshot(), allow_nan=False)  # no Infinity in /slo
    for _ in range(2):
        eng.record("submit", 500, 1.0)
    assert eng.breached_routes() == ["submit"]
    declared = SloEngine.from_config(
        ObservabilityConfig(slo_routes="submit:latency_ms=1000")
    )
    for _ in range(2):
        declared.record("submit", 200, 22_000.0)
    assert declared.breached_routes() == ["submit"]


# -- histogram exemplars -------------------------------------------------------

#: OpenMetrics exemplar-annotated bucket sample:
#: name{...,le="X"} N # {trace_id="..."} value [timestamp]
EXEMPLAR_LINE = re.compile(
    r'^[a-zA-Z_][a-zA-Z0-9_]*_bucket\{[^{}]*le="[^"]+"\} \d+'
    r' # \{trace_id="[A-Za-z0-9_.\-]+"\}'
    r" -?\d+(\.\d+)?([eE][+-]?\d+)? \d+(\.\d+)?$"
)


@obs
def test_histogram_exemplar_records_bucket_and_trace():
    h = Histogram("t.lat_ms", label="route", exemplars=True)
    with request_context(RequestContext(trace_id="trace01")):
        h.observe(3.0, label_value="a")
    h.observe(700.0, label_value="a", exemplar="trace02")
    h.observe(5.0, label_value="a")  # no context, no explicit id: none
    series = h.collect()["a"]
    ex = series["exemplars"]
    assert ex["5"]["traceId"] == "trace01"
    assert ex["1000"]["traceId"] == "trace02"
    assert ex["1000"]["value"] == 700.0
    # the most recent observation in a bucket wins its exemplar slot
    h.observe(2.9, label_value="a", exemplar="trace03")
    assert h.collect()["a"]["exemplars"]["5"]["traceId"] == "trace03"


@obs
def test_exemplar_openmetrics_syntax_valid():
    reg = MetricsRegistry()
    h = reg.histogram("req.lat_ms", label="route", exemplars=True)
    h.observe(42.0, label_value="g_variants", exemplar="abcd1234")
    text = reg.render_prometheus(openmetrics=True)
    annotated = [ln for ln in text.splitlines() if " # {" in ln]
    assert annotated, text
    for ln in annotated:
        assert EXEMPLAR_LINE.match(ln), f"bad exemplar syntax: {ln!r}"
    assert text.rstrip().endswith("# EOF")
    # the classic text format's parsers reject exemplar syntax, so the
    # default render must omit them (and the EOF terminator)
    classic = reg.render_prometheus()
    assert " # {" not in classic and "# EOF" not in classic


@obs
def test_exemplars_off_by_default():
    h = Histogram("t.plain_ms")
    with request_context(RequestContext(trace_id="t")):
        h.observe(1.0)
    assert "exemplars" not in h.collect()[""]


# -- EventJournal unit ---------------------------------------------------------


@obs
def test_event_journal_publish_filter_and_bounds():
    j = EventJournal(keep=4)
    for k in range(6):
        j.publish("breaker.open", route=f"w{k}")
    j.publish("dispatch.failover", to="w9")
    assert j.published() == 7 and j.last_seq() == 7
    evs = j.events()
    assert len(evs) == 4  # bounded ring
    assert [e["seq"] for e in evs] == [4, 5, 6, 7]
    # since + kind-prefix filters
    assert [e["seq"] for e in j.events(since=5)] == [6, 7]
    assert all(
        e["kind"] == "breaker.open" for e in j.events(kind="breaker")
    )
    assert j.events(kind="dispatch")[0]["data"] == {"to": "w9"}
    assert j.events(kind="nope") == []


@obs
def test_event_journal_stamps_ambient_trace_id():
    j = EventJournal()
    with request_context(RequestContext(trace_id="ctxtrace")):
        j.publish("breaker.open", route="w")
    j.publish("breaker.close", route="w")
    evs = j.events()
    assert evs[0]["traceId"] == "ctxtrace"
    assert "traceId" not in evs[1]
    assert evs[0]["tMono"] <= evs[1]["tMono"]
    assert evs[0]["time"] > 0


@obs
def test_event_journal_disable_and_reconfigure():
    j = EventJournal(keep=8, enabled=False)
    assert j.publish("breaker.open") is None
    assert j.published() == 0
    j.configure(enabled=True)
    j.publish("breaker.open")
    j.publish("breaker.close")
    j.configure(keep=1)  # shrink preserves the newest entries
    assert [e["kind"] for e in j.events()] == ["breaker.close"]


# -- endpoint schema stability (GOLDEN shapes) ---------------------------------


@pytest.fixture()
def app():
    from sbeacon_tpu.api import BeaconApp

    app = BeaconApp()
    try:
        yield app
    finally:
        app.close()


@obs
def test_slo_endpoint_schema(app):
    app.handle("GET", "/info")
    app.handle("GET", "/map")
    status, doc = app.handle("GET", "/slo")
    assert status == 200
    assert set(doc) == {"alertBurnRate", "windows", "routes"}
    assert doc["windows"] == {"5m": 300.0, "1h": 3600.0}
    route = doc["routes"]["info"]
    assert set(route) == {"availability", "latency", "breached"}
    avail = route["availability"]
    assert set(avail) == {"windows", "breached", "target"}
    lat = route["latency"]
    assert set(lat) == {"windows", "breached", "target", "thresholdMs"}
    for kind in (avail, lat):
        for wname in ("5m", "1h"):
            win = kind["windows"][wname]
            assert set(win) == {
                "good", "bad", "total", "badRatio", "burnRate",
            }
    assert avail["windows"]["5m"]["good"] >= 1
    # probe routes never carry objectives
    assert "metrics" not in doc["routes"]
    assert "slo" not in doc["routes"]


@obs
def test_slo_gauges_render_with_route_and_window_labels(app):
    app.handle("GET", "/info")
    status, text = app.handle("GET", "/metrics", {"format": "prometheus"})
    assert status == 200
    assert "# TYPE sbeacon_slo_burn_rate gauge" in text
    assert 'sbeacon_slo_burn_rate{route="info",window="5m"} 0' in text
    assert 'sbeacon_slo_burn_rate{route="info",window="1h"} 0' in text
    assert "# TYPE sbeacon_slo_latency_burn_rate gauge" in text
    assert 'sbeacon_slo_breached{route="info"} 0' in text
    # and the JSON twin nests by route then window
    _, body = app.handle("GET", "/metrics")
    assert body["slo"]["burn_rate"]["info"]["5m"] == 0.0


@obs
def test_ops_events_endpoint_schema(app):
    seq0 = journal.last_seq()
    publish_event("breaker.open", route="http://w1:1")
    publish_event("dispatch.failover", failed="http://w1:1", to="http://w2:1")
    status, doc = app.handle("GET", "/ops/events", {"since": str(seq0)})
    assert status == 200
    assert set(doc) == {
        "events", "nextSince", "lastSeq", "published", "enabled",
    }
    assert doc["lastSeq"] >= seq0 + 2
    # caught up: the resume cursor jumps to the journal head
    assert doc["nextSince"] == doc["lastSeq"]
    kinds = [e["kind"] for e in doc["events"]]
    assert "breaker.open" in kinds and "dispatch.failover" in kinds
    for e in doc["events"]:
        assert {"seq", "kind", "tMono", "time"} <= set(e)
    # kind filter + since tailing
    status, doc = app.handle(
        "GET", "/ops/events", {"since": str(seq0), "kind": "dispatch"}
    )
    assert [e["kind"] for e in doc["events"]] == ["dispatch.failover"]
    assert doc["events"][0]["data"]["to"] == "http://w2:1"
    # malformed query params answer 400, not 500
    status, doc = app.handle("GET", "/ops/events", {"since": "bogus"})
    assert status == 400 and "error" in doc


@obs
def test_debug_status_schema_and_diagnosis(app):
    app.handle("GET", "/info")
    status, doc = app.handle("GET", "/debug/status")
    assert status == 200
    assert set(doc) == {
        "ready", "beaconId", "slo", "breakers", "routing", "queues",
        "ingest", "stages", "filters", "requests", "engine", "costs",
        "canary", "device", "events", "plans", "diagnosis",
    }
    assert set(doc["engine"]["materialized"]) == {
        "skipped", "inline", "pooled",
    }
    assert set(doc["filters"]["memo"]) == {
        "hits", "misses", "invalidations", "entries",
    }
    # canary rollup (ISSUE 12): the prober exists (idle) on every app
    assert doc["canary"]["registeredProbes"] == 0
    assert doc["canary"]["mismatches"] == 0
    # ingest-while-serving rollup (ISSUE 10): delta-tail depth +
    # compactor counters; empty tails render as {}
    assert set(doc["ingest"]) <= {"deltaTails", "l0", "compactor"}
    assert doc["ready"] is True
    assert set(doc["queues"]) == {
        "admission", "shaping", "runner", "batcher",
    }
    assert doc["queues"]["admission"]["in_flight"] == 0
    assert doc["queues"]["shaping"]["brownoutLevel"] == 0
    assert "materialize_ms" in doc["stages"]
    assert "admission_wait_ms" in doc["stages"]
    # cost-accounting rollup (ISSUE 11): the /info request above is a
    # tracked route, so at least one request folded
    assert doc["costs"]["requests"] >= 1
    assert "costliestTenant" in doc["costs"]
    # device-plane rollup (ISSUE 14): launch decomposition + padding
    # waste + mid-request compile count ride the same document
    assert set(doc["device"]) == {
        "launches", "padWaste", "midRequestCompiles", "placement",
    }
    assert doc["device"]["launches"]["total"] >= 0
    assert set(doc["diagnosis"]) == {
        "breachedSlos", "openBreakers", "slowestStage", "slowestWorker",
        "costliestTenant", "costliestShape", "canaryMismatches",
        "worstPadWaste", "midRequestCompiles", "lastMidRequestCompile",
        "planDrift",
    }
    assert set(doc["events"]) == {"lastSeq", "published"}
    # single-host app: no worker routing section content
    assert doc["routing"] == {}


@obs
def test_debug_status_names_slowest_stage(app):
    # feed the runner's admission-wait ring so a stage has quantiles
    # (the runner.wait stage of the process-wide tracer: start it empty)
    from sbeacon_tpu.utils.trace import tracer

    tracer.reset_stages()
    app.query_runner._note_queue_wait(125.0)
    _, doc = app.handle("GET", "/debug/status")
    assert doc["stages"]["admission_wait_ms"]["p50"] == 125.0
    assert doc["diagnosis"]["slowestStage"] == "admission_wait_ms"


# -- per-tenant SLO views (ISSUE 11) -------------------------------------------


@obs
def test_slo_tenant_view_golden_schema(app):
    """/slo?tenant= serves the SAME burn-rate document shape, scoped
    to one tenant's isolated rings, plus a 'tenant' field naming the
    scope — and the unscoped /slo document is unchanged."""
    app.handle(
        "GET", "/g_variants", None, None, {"X-Beacon-Tenant": "gold"}
    )
    status, doc = app.handle("GET", "/slo", {"tenant": "gold"})
    assert status == 200
    assert set(doc) == {"alertBurnRate", "windows", "routes", "tenant"}
    assert doc["tenant"] == "gold"
    route = doc["routes"]["g_variants"]
    assert set(route) == {"availability", "latency", "breached"}
    for kind in ("availability", "latency"):
        for wname in ("5m", "1h"):
            win = route[kind]["windows"][wname]
            assert set(win) == {
                "good", "bad", "total", "badRatio", "burnRate",
            }
    assert route["availability"]["windows"]["5m"]["total"] >= 1
    # a tenant with no recorded traffic serves an empty routes map,
    # same schema — never a 404/500
    status, doc = app.handle("GET", "/slo", {"tenant": "nobody"})
    assert status == 200 and doc["routes"] == {}
    # and the global document keeps its exact historical shape
    status, doc = app.handle("GET", "/slo")
    assert set(doc) == {"alertBurnRate", "windows", "routes"}


@obs
def test_slo_tenant_burn_isolation():
    """Tenant A's 5xx storm must not move tenant B's burn view (and
    both fold into the global rings)."""
    clk = [0.0]
    eng = _engine_at(clk)
    for _ in range(10):
        eng.record("g_variants", 500, 1.0, tenant="storm")
    for _ in range(10):
        eng.record("g_variants", 200, 1.0, tenant="calm")
    storm = eng.snapshot(tenant="storm")["routes"]["g_variants"]
    calm = eng.snapshot(tenant="calm")["routes"]["g_variants"]
    assert storm["availability"]["windows"]["5m"]["bad"] == 10
    assert storm["availability"]["windows"]["5m"]["burnRate"] > 0
    assert calm["availability"]["windows"]["5m"]["bad"] == 0
    assert calm["availability"]["windows"]["5m"]["burnRate"] == 0.0
    assert calm["availability"]["windows"]["5m"]["good"] == 10
    # the global view aggregates both
    glob = eng.snapshot()["routes"]["g_variants"]
    assert glob["availability"]["windows"]["5m"]["total"] == 20
    assert eng.tenants() == ["calm", "storm"]


@obs
def test_slo_tenant_probe_route_exclusion_and_cardinality_cap():
    clk = [0.0]
    eng = _engine_at(clk, max_tenants=2)
    # probe routes never carry objectives — tenant scoping included
    eng.record("metrics", 500, 1.0, tenant="t0")
    eng.record("ops.events", 500, 1.0, tenant="t0")
    assert eng.snapshot(tenant="t0")["routes"] == {}
    # cardinality: past max_tenants, new ids share the overflow bucket
    for t in ("t0", "t1", "t2", "t3"):
        eng.record("g_variants", 200, 1.0, tenant=t)
    assert set(eng.tenants()) == {"t0", "t1", "overflow"}
    over = eng.snapshot(tenant="t2")
    assert over["tenant"] == "overflow"
    assert (
        over["routes"]["g_variants"]["availability"]["windows"]["5m"][
            "total"
        ]
        == 2  # t2 and t3 both landed in the shared bucket
    )


@obs
def test_slo_from_config_threads_the_shaping_tenant_cap():
    """BEACON_MAX_TENANTS must bound EVERY tenant plane at the same
    count: from_config threads shaping's cap into the SLO engine
    (review fix — a fixed 64 here diverged from /ops/costs)."""
    eng = SloEngine.from_config(ObservabilityConfig(), max_tenants=2)
    assert eng.max_tenants == 2
    for t in ("t0", "t1", "t2"):
        eng.record("g_variants", 200, 1.0, tenant=t)
    assert set(eng.tenants()) == {"t0", "t1", "overflow"}


@obs
def test_tenant_slo_rides_the_tenant_header_through_the_api(app):
    app.handle(
        "GET", "/g_variants", None, None, {"X-Beacon-Tenant": "acme"}
    )
    _, doc = app.handle("GET", "/slo", {"tenant": "acme"})
    assert "g_variants" in doc["routes"]


# -- /ops/events kind list (ISSUE 11 satellite) --------------------------------


@obs
def test_event_journal_kind_accepts_comma_list():
    """Operators correlating two control planes (compaction vs
    brownout) tail ONE interleaved stream: ?kind=a,b matches either,
    each by the usual exact-or-prefix rule."""
    j = EventJournal(keep=16)
    j.publish("compaction.start", dataset="d0")
    j.publish("shaping.brownout", level=1)
    j.publish("breaker.open", route="w1")
    j.publish("compaction.complete", dataset="d0")
    kinds = [
        e["kind"]
        for e in j.events(kind="compaction,shaping.brownout")
    ]
    assert kinds == [
        "compaction.start", "shaping.brownout", "compaction.complete",
    ]
    # single-filter behaviour unchanged; whitespace tolerated
    assert [e["kind"] for e in j.events(kind="breaker")] == [
        "breaker.open"
    ]
    assert [
        e["kind"] for e in j.events(kind=" compaction , nope ")
    ] == ["compaction.start", "compaction.complete"]


@obs
def test_ops_events_kind_list_through_the_api(app):
    seq0 = journal.last_seq()
    publish_event("compaction.start", dataset="dx")
    publish_event("shaping.brownout", level=2)
    publish_event("breaker.open", route="wz")
    status, doc = app.handle(
        "GET",
        "/ops/events",
        {"since": str(seq0), "kind": "compaction,shaping.brownout"},
    )
    assert status == 200
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds == ["compaction.start", "shaping.brownout"]


# -- the acceptance integration ------------------------------------------------


def _records(seed=5, n=200):
    rng = random.Random(seed)
    return random_records(rng, chrom="21", n=n, n_samples=2)


def _replica_engine(recs, ds="rz"):
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(microbatch=False)))
    eng.add_index(
        build_index(
            recs,
            dataset_id=ds,
            vcf_location=f"synthetic://{ds}",
            sample_names=["A", "B"],
        )
    )
    return eng


def _hit_alt(rec):
    for a, ac in zip(rec.alts, rec.effective_ac()):
        if re.fullmatch(r"[ACGTN]+", a) and ac > 0:
            return a
    return None


def _gv_query(rec):
    return {
        "query": {
            "requestedGranularity": "boolean",
            "requestParameters": {
                "assemblyId": "GRCh38",
                "referenceName": "21",
                "start": [max(0, rec.pos - 1)],
                "end": [rec.pos + len(rec.ref) + 5],
                "alternateBases": _hit_alt(rec),
            },
        }
    }


@obs
def test_kill_failover_heal_event_sequence_burn_and_exemplar(tmp_path):
    """The ISSUE 7 acceptance scenario: kill every replica of a dataset
    under strict (no-partial-results) mode, query, restart, and verify
    (a) /ops/events carries the breaker.open -> dispatch.failover ->
    routing.rediscovery/breaker.close sequence, (b) /slo shows an
    availability burn-rate rise on g_variants, (c) the failed request's
    latency exemplar carries its trace id and that id resolves to a
    span tree at /_trace."""
    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer
    from sbeacon_tpu.utils.trace import tracer

    recs = _records()
    q = [r for r in recs if _hit_alt(r)]
    w1 = WorkerServer(_replica_engine(recs)).start_background()
    w2 = WorkerServer(_replica_engine(recs)).start_background()
    host2, port2 = w2.server.server_address[:2]

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "coord"),
        engine=EngineConfig(use_mesh=False, microbatch=False),
        resilience=ResilienceConfig(
            breaker_failure_threshold=1, partial_results=False
        ),
    )
    cfg.storage.ensure()
    dist = DistributedEngine(
        [w1.address, w2.address],
        local=VariantEngine(cfg),
        config=cfg,
        retries=0,
        timeout_s=10.0,
    )
    dist.REDISCOVERY_INTERVAL_S = 0.1
    app = BeaconApp(cfg, engine=dist)
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
    wb = None
    tracer.enable()
    try:
        seq0 = journal.last_seq()
        # healthy baseline: a provable hit, zero burn on the route
        status, body = app.handle(
            "POST", "/g_variants", body=_gv_query(q[0])
        )
        assert status == 200
        assert body["responseSummary"]["exists"] is True
        _, slo0 = app.handle("GET", "/slo")
        gv0 = slo0["routes"]["g_variants"]["availability"]["windows"]
        assert gv0["5m"]["burnRate"] == 0.0

        # kill EVERY replica: strict mode must surface 5xx after the
        # failover walk exhausts the copies
        w1.shutdown()
        w2.shutdown()
        tid = new_trace_id()
        status, body = app.handle(
            "POST",
            "/g_variants",
            body=_gv_query(q[1]),
            headers={"X-Beacon-Trace": tid},
        )
        assert status >= 500, body
        # satellite: the error envelope carries the trace id too
        assert body["meta"]["traceId"] == tid

        # (a) event sequence so far: a breaker opened, a failover was
        # attempted to the sibling replica
        _, ev = app.handle("GET", "/ops/events", {"since": str(seq0)})
        kinds = [e["kind"] for e in ev["events"]]
        assert "breaker.open" in kinds, kinds
        assert "dispatch.failover" in kinds, kinds
        assert kinds.index("breaker.open") < kinds.index(
            "dispatch.failover"
        )
        fo = next(
            e for e in ev["events"] if e["kind"] == "dispatch.failover"
        )
        assert fo["traceId"] == tid  # stamped from the request context

        # (b) the availability burn rose on the affected route
        _, slo1 = app.handle("GET", "/slo")
        gv1 = slo1["routes"]["g_variants"]["availability"]["windows"]
        assert gv1["5m"]["burnRate"] > 0.0
        assert gv1["1h"]["burnRate"] > 0.0
        assert gv1["5m"]["bad"] >= 1

        # (c) the request's latency exemplar carries its trace id and
        # resolves to a span tree at /_trace
        _, metrics = app.handle("GET", "/metrics")
        exemplars = metrics["request"]["latency_ms"]["g_variants"][
            "exemplars"
        ]
        assert any(e["traceId"] == tid for e in exemplars.values()), (
            exemplars
        )
        status, trace_doc = app.handle(
            "GET", "/_trace", {"trace_id": tid}
        )
        assert status == 200
        assert trace_doc["traces"], "trace id did not resolve at /_trace"
        assert all(t["traceId"] == tid for t in trace_doc["traces"])

        # heal: restart a replica at w2's address; rediscovery (0.1 s
        # cadence) republishes and the breaker closes
        wb = WorkerServer(
            _replica_engine(recs), host=host2, port=port2
        ).start_background()
        t_end = time.time() + 10
        healed = False
        while time.time() < t_end and not healed:
            status, body = app.handle(
                "POST", "/g_variants", body=_gv_query(q[1])
            )
            healed = (
                status == 200
                and body["responseSummary"]["exists"] is True
            )
            if not healed:
                time.sleep(0.2)
        assert healed, body

        _, ev = app.handle(
            "GET", "/ops/events", {"since": str(seq0), "limit": "512"}
        )
        kinds = [e["kind"] for e in ev["events"]]
        assert "routing.rediscovery" in kinds, kinds
        assert "breaker.close" in kinds, kinds
        # the heal comes after the outage: first open < first close
        assert kinds.index("breaker.open") < kinds.index("breaker.close")
        assert "routing.table_publish" in kinds  # initial discovery
        # and /debug/status reflects the healed topology
        _, dbg = app.handle("GET", "/debug/status")
        assert dbg["routing"]["replicas"] >= 1
        assert dbg["routing"]["tableAgeS"] is not None
        assert wb.address in dbg["routing"]["workers"]
    finally:
        tracer.disable()
        if wb is not None:
            wb.shutdown()
        dist.close()
        app.close()
