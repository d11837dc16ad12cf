"""Telemetry plane: typed registry, renderings, schema stability,
request context, slow-query log, and the metric-name lint (ISSUE 4)."""

import json
import re
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from sbeacon_tpu.telemetry import (
    LATENCY_BUCKETS_MS,
    MetricsRegistry,
    RequestContext,
    SlowQueryLog,
    annotate,
    current_context,
    new_trace_id,
    request_context,
)

obs = pytest.mark.obs

REPO = Path(__file__).resolve().parent.parent


# -- registry unit ------------------------------------------------------------


@obs
def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("t.hits")
    g = reg.gauge("t.depth")
    h = reg.histogram("t.lat_ms")
    c.inc()
    c.inc(2)
    g.set(7)
    h.observe(3.0)
    h.observe(9999.0)
    h.observe(1e9)  # overflow bucket
    j = reg.render_json()
    assert j["t"]["hits"] == 3
    assert j["t"]["depth"] == 7
    hist = j["t"]["lat_ms"]
    assert hist["count"] == 3
    assert hist["buckets"]["+Inf"] == 3
    # cumulative: everything <= 10000 bucket except the 1e9 outlier
    assert hist["buckets"]["10000"] == 2


@obs
def test_registry_rejects_duplicates_and_bad_names():
    reg = MetricsRegistry()
    reg.counter("a.b")
    with pytest.raises(ValueError):
        reg.counter("a.b")
    with pytest.raises(ValueError):
        reg.counter("nodots")
    with pytest.raises(ValueError):
        reg.gauge("Upper.Case")


@obs
def test_labeled_series_and_callback_instruments():
    reg = MetricsRegistry()
    c = reg.counter("t.by_route", label="route")
    c.inc(label_value="a")
    c.inc(2, label_value="b")
    reg.gauge("t.live", fn=lambda: 42)
    j = reg.render_json()
    assert j["t"]["by_route"] == {"a": 1, "b": 2}
    assert j["t"]["live"] == 42
    text = reg.render_prometheus()
    assert 'sbeacon_t_by_route{route="a"} 1' in text
    assert "sbeacon_t_live 42" in text


@obs
def test_broken_callback_does_not_kill_render():
    reg = MetricsRegistry()
    reg.gauge("t.bad", fn=lambda: 1 / 0)
    reg.gauge("t.good", fn=lambda: 1)
    assert reg.render_json()["t"]["good"] == 1
    assert "sbeacon_t_good 1" in reg.render_prometheus()


_NUM = r"-?\d+(\.\d+)?([eE][+-]?\d+)?"
_SAMPLE = re.compile(
    r"^[a-zA-Z_][a-zA-Z0-9_]*(\{[^{}]*\})? " + _NUM
    # optional OpenMetrics exemplar: ` # {trace_id="..."} value [ts]`
    + r"( # \{[^{}]*\} " + _NUM + r"( " + _NUM + r")?)?$"
)


def _assert_valid_exposition(text: str) -> dict:
    """Minimal Prometheus text-format parser: every non-comment line is
    ``name{labels} value`` with an optional OpenMetrics exemplar
    suffix; returns {metric_name: n_samples}."""
    seen: dict = {}
    for line in text.strip().splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE.match(line), f"invalid exposition line: {line!r}"
        name = line.split("{")[0].split(" ")[0]
        seen[name] = seen.get(name, 0) + 1
    return seen


@obs
def test_prometheus_rendering_parses_with_histograms():
    reg = MetricsRegistry()
    h = reg.histogram("req.lat_ms", label="route")
    h.observe(3.0, label_value="g_variants")
    h.observe(700.0, label_value="g_variants")
    h.observe(1.0, label_value="info")
    seen = _assert_valid_exposition(reg.render_prometheus())
    # one bucket series per boundary (+Inf) per route, plus sum/count
    assert seen["sbeacon_req_lat_ms_bucket"] == 2 * (
        len(LATENCY_BUCKETS_MS) + 1
    )
    assert seen["sbeacon_req_lat_ms_sum"] == 2
    assert seen["sbeacon_req_lat_ms_count"] == 2


@obs
def test_openmetrics_counter_samples_get_total_suffix():
    """OpenMetrics requires counter samples named <family>_total; the
    classic format rejects that form — each dialect must render its
    own naming, or a strict scraper fails the whole scrape."""
    reg = MetricsRegistry()
    reg.counter("t.hits", fn=lambda: 3)
    reg.counter("t.by_route", label="route", fn=lambda: {"a": 1})
    om = reg.render_prometheus(openmetrics=True)
    assert "sbeacon_t_hits_total 3" in om
    assert 'sbeacon_t_by_route_total{route="a"} 1' in om
    assert "# TYPE sbeacon_t_hits counter" in om  # family keeps its name
    classic = reg.render_prometheus()
    assert "sbeacon_t_hits 3" in classic and "_total" not in classic


# -- /metrics schema stability (golden keys) ----------------------------------

#: the documented metric catalogue (DEPLOYMENT.md "Observability"):
#: renaming any of these must break CI here, not dashboards
GOLDEN_METRICS = [
    "request.latency_ms",
    "request.slow_queries",
    "admission.max_in_flight",
    "admission.in_flight",
    "admission.admitted",
    "admission.shed",
    "runner.workers",
    "runner.max_pending",
    "runner.active",
    "runner.shed",
    "batcher.submits",
    "batcher.specs",
    "batcher.launches",
    "batcher.mean_batch",
    "batcher.expired",
    "batcher.timeouts",
    "batcher.histogram",
    "batcher.fused_hist",
    "batcher.launcher.threads",
    "batcher.launcher.queued",
    "batcher.fetcher.threads",
    "batcher.fetcher.queued",
    "batcher.queue_wait_ms",
    "batcher.exec_ms",
    "batcher.encode_ms",
    "batcher.launch_ms",
    "batcher.fetch_ms",
    "engine.fused_searches",
    "engine.mesh_searches",
    "engine.fanout_targets",
    "engine.materialized",
    "engine.selected_samples",
    "engine.materialize_ms",
    "response_cache.entries",
    "response_cache.max_entries",
    "response_cache.ttl_s",
    "response_cache.hits",
    "response_cache.misses",
    "response_cache.hit_rate",
    "response_cache.negative_hits",
    "response_cache.evictions",
    "response_cache.expirations",
    "response_cache.invalidations",
    "response_cache.scoped_invalidations",
    "ingest.delta_publishes",
    "ingest.delta_shards",
    "ingest.l0_builds",
    "ingest.l0_key_builds",
    "ingest.l0_block_reuses",
    "ingest.l0_served_queries",
    "ingest.slice_disk_bytes",
    "ingest.gc_bytes",
    "ingest.native_fallbacks",
    "compaction.runs",
    "compaction.folded_rows",
    "compaction.tier_folds",
    "compaction.write_amplification",
    "transport.conn.opened",
    "transport.conn.reused",
    "transport.conn.evicted",
    "transport.conn.retried",
    "transport.gzip_bodies",
    "transport.hedges",
    "transport.rtt_ms",
    "dispatch.short_circuits",
    "dispatch.failovers",
    "dispatch.partial_responses",
    "routing.replicas",
    "routing.rediscoveries",
    "breaker.state",
    "breaker.consecutive_failures",
    "breaker.opens",
    "batcher.stage_ms",
    "runner.queue_wait_ms",
    "slo.burn_rate",
    "slo.latency_burn_rate",
    "slo.breached",
    "events.published",
    "cost.requests",
    "cost.units",
    "cost.device_us",
    "cost.host_rows",
    "cost.worker_rtt_ms",
    "cost.response_bytes",
    "cost.shape_units",
    "telemetry.label_overflow",
    "fleet.digest_polls",
    "fleet.workers_reachable",
    "fleet.divergent_datasets",
    "canary.probes",
    "canary.mismatches",
    "canary.failures",
    "canary.slow_probes",
    "plan.sampled",
    "plan.shapes",
    "plan.drift",
    "device.launches",
    "device.evaluated_pairs",
    "device.pad_waste",
    "device.mid_request_compiles",
    "device.fetched_bytes",
    "device.plane_gather_bytes",
    "device.plane_resident_bytes",
    "device.plane_fill",
    "device.donated_buffers",
    "device.query_uploads",
    "device.fallbacks",
    "migration.started",
    "migration.completed",
    "migration.rolled_back",
    "migration.bytes_copied",
    "runner.submits",
    "runner.memory_hits",
    "runner.table_hits",
    "runner.persisted_jobs",
    "runner.persist_commits",
    "runner.persist_expired",
    "runner.persist_queue",
    "runtime.gc_pauses",
    "runtime.gc_pause_ms",
    "runtime.thread_cpu_ms",
    "runtime.thread_yields",
    "runtime.thread_preempted",
    "runtime.process_cpu_ms",
    "runtime.host_cpus",
    "runtime.stage_cpu_every",
]


@pytest.fixture()
def app():
    from sbeacon_tpu.api import BeaconApp

    app = BeaconApp()
    try:
        yield app
    finally:
        app.close()


@obs
def test_metrics_golden_keys_registered(app):
    missing = [n for n in GOLDEN_METRICS if n not in app.telemetry.names()]
    assert not missing, f"documented metrics missing: {missing}"


@obs
def test_metrics_json_rendering_keeps_golden_paths(app):
    status, body = app.handle("GET", "/metrics")
    assert status == 200
    # breaker renders in its historical per-route JSON shape (or not at
    # all on single-host engines), so it is Prometheus-only here
    for name in GOLDEN_METRICS:
        if name.startswith("breaker."):
            continue
        node = body
        for part in name.split("."):
            assert isinstance(node, dict) and part in node, (
                f"/metrics JSON lost {name} at {part!r}"
            )
            node = node[part]


@obs
def test_metrics_prometheus_rendering_keeps_golden_names(app):
    status, text = app.handle("GET", "/metrics", {"format": "prometheus"})
    assert status == 200 and isinstance(text, str)
    _assert_valid_exposition(text)
    for name in GOLDEN_METRICS:
        pname = "sbeacon_" + name.replace(".", "_")
        assert f"# TYPE {pname} " in text, f"exposition lost {pname}"


@obs
def test_metrics_prometheus_via_accept_header(app):
    status, text = app.handle(
        "GET", "/metrics", None, None, {"Accept": "text/plain"}
    )
    assert status == 200 and isinstance(text, str)
    assert "sbeacon_admission_in_flight" in text


@obs
def test_request_latency_histogram_per_route(app):
    app.handle("GET", "/info")
    app.handle("GET", "/map")
    app.handle("GET", "/does-not-exist")
    # diagnostic heads only label their KNOWN endpoints: a scanner
    # walking /ops/<random> must not mint histogram series
    app.handle("GET", "/ops/scan-a")
    app.handle("GET", "/debug/scan-b")
    _, body = app.handle("GET", "/metrics")
    lat = body["request"]["latency_ms"]
    assert "info" in lat and "map" in lat and "other" in lat
    assert lat["info"]["count"] >= 1
    assert not any(
        k.startswith(("ops.", "debug."))
        and k not in ("ops.events", "debug.status")
        for k in lat
    ), sorted(lat)
    _, text = app.handle("GET", "/metrics", {"format": "prometheus"})
    assert 'sbeacon_request_latency_ms_bucket{route="info",le="+Inf"}' in text


@obs
def test_malformed_inbound_trace_id_is_replaced(app):
    # the inbound value is re-emitted into outbound worker headers and
    # log lines: junk (oversized, control chars) must not pass through
    for bad in ("x" * 200, "evil\r\nInjected: 1", ""):
        _, body = app.handle(
            "GET", "/info", None, None, {"X-Beacon-Trace": bad}
        )
        tid = body["meta"]["traceId"]
        assert tid != bad and re.fullmatch(r"[0-9a-f]{16}", tid)


@obs
def test_trace_id_minted_and_honored_in_envelope(app):
    _, body = app.handle("GET", "/info")
    tid = body["meta"]["traceId"]
    assert re.fullmatch(r"[0-9a-f]{16}", tid)
    assert body["meta"]["elapsedTimeMs"] >= 0
    want = new_trace_id()
    _, body = app.handle(
        "GET", "/info", None, None, {"X-Beacon-Trace": want}
    )
    assert body["meta"]["traceId"] == want


# -- request context ----------------------------------------------------------


@obs
def test_request_context_scoping_and_annotate():
    assert current_context() is None
    annotate(ignored=True)  # no ambient context: must be a no-op
    ctx = RequestContext(route="g_variants")
    with request_context(ctx):
        assert current_context() is ctx
        annotate(response_cache="hit")
        inner = RequestContext()
        with request_context(inner):
            assert current_context() is inner
        assert current_context() is ctx
    assert current_context() is None
    assert ctx.notes == {"response_cache": "hit"}


@obs
def test_request_context_is_thread_local():
    ctx = RequestContext()
    seen = {}

    def other():
        seen["ctx"] = current_context()

    with request_context(ctx):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["ctx"] is None


# -- slow-query log -----------------------------------------------------------


@obs
def test_slow_query_log_threshold_and_ring(tmp_path):
    path = tmp_path / "slow.jsonl"
    slog = SlowQueryLog(threshold_ms=5.0, keep=2, path=str(path))
    assert not slog.maybe_record(
        trace_id="t1", route="info", status=200, elapsed_ms=1.0
    )
    for k in range(3):
        assert slog.maybe_record(
            trace_id=f"t{k}",
            route="g_variants",
            status=200,
            elapsed_ms=10.0 + k,
            notes={"response_cache": "miss"},
        )
    assert slog.count() == 3
    recent = slog.recent()
    assert len(recent) == 2  # ring bounded by keep
    assert recent[-1]["traceId"] == "t2"
    assert recent[-1]["notes"] == {"response_cache": "miss"}
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [e["traceId"] for e in lines] == ["t0", "t1", "t2"]


@obs
def test_slow_query_log_disabled_and_log_everything():
    off = SlowQueryLog(threshold_ms=-1.0)
    assert not off.maybe_record(
        trace_id="t", route="r", status=200, elapsed_ms=1e9
    )
    everything = SlowQueryLog(threshold_ms=0.0)
    assert everything.maybe_record(
        trace_id="t", route="r", status=200, elapsed_ms=0.01
    )


@obs
def test_slow_query_fires_through_the_api(tmp_path):
    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.config import (
        BeaconConfig,
        ObservabilityConfig,
        StorageConfig,
    )

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "store"),
        observability=ObservabilityConfig(slow_query_ms=0.0),
    )
    cfg.storage.ensure()
    app = BeaconApp(cfg)
    try:
        _, body = app.handle("GET", "/info")
        tid = body["meta"]["traceId"]
        entries = app.slow_log.recent()
        assert entries and entries[-1]["traceId"] == tid
        assert entries[-1]["route"] == "info"
        _, m = app.handle("GET", "/metrics")
        assert m["request"]["slow_queries"] >= 1
    finally:
        app.close()


# -- error envelopes carry the trace id (ISSUE 7 satellite) -------------------


@obs
def test_error_envelopes_carry_trace_id(app):
    """EVERY error envelope — 4xx and 5xx alike — must stamp
    meta.traceId (and honor an inbound X-Beacon-Trace) exactly like the
    happy path: a failed request is the one whose trace the operator
    needs most."""
    want = new_trace_id()
    hdr = {"X-Beacon-Trace": want}

    # 404 unknown path
    status, body = app.handle("GET", "/no-such-path/x", None, None, hdr)
    assert status == 404
    assert body["meta"]["traceId"] == want
    assert body["meta"]["elapsedTimeMs"] >= 0

    # 400 malformed deadline header
    status, body = app.handle(
        "GET", "/g_variants", None, None,
        {"X-Beacon-Trace": want, "X-Beacon-Deadline": "bogus"},
    )
    assert status == 400 and body["meta"]["traceId"] == want

    # 429 admission shed
    from sbeacon_tpu.resilience import AdmissionController

    app.admission = AdmissionController(1)
    assert app.admission.try_acquire()  # occupy the only slot
    try:
        status, body = app.handle("GET", "/g_variants", None, None, hdr)
        assert status == 429, body
        assert body["meta"]["traceId"] == want
        assert body["retryAfterSeconds"] > 0
    finally:
        app.admission.release()

    # 5xx: a store blow-up must still produce a trace-stamped envelope
    def boom(*a, **kw):
        raise RuntimeError("injected store failure")

    app.store.filtering_terms = boom
    status, body = app.handle("GET", "/filtering_terms", None, None, hdr)
    assert status == 500 and body["meta"]["traceId"] == want

    # error envelopes without an inbound id still mint one
    status, body = app.handle("GET", "/no-such-path")
    assert status == 404
    assert re.fullmatch(r"[0-9a-f]{16}", body["meta"]["traceId"])


# -- label-cardinality guard (ISSUE 11 satellite) ------------------------------


@obs
def test_label_cardinality_guard_counter_collapses_to_other():
    """A value-owning labeled series mints at most max_label_values
    distinct labels; overflow collapses to 'other' and ticks
    telemetry.label_overflow{family=...} — the registry-level twin of
    shaping's tenant cap, so NO producer can mint unbounded series."""
    reg = MetricsRegistry()
    c = reg.counter("t.by_tenant", label="tenant", max_label_values=4)
    for k in range(10):
        c.inc(label_value=f"tenant{k}")
    j = reg.render_json()
    series = j["t"]["by_tenant"]
    assert len(series) == 5  # 4 real + the shared "other"
    assert series["other"] == 6
    # established label values keep accumulating after the cap
    c.inc(label_value="tenant0")
    assert reg.render_json()["t"]["by_tenant"]["tenant0"] == 2
    overflow = reg.render_json()["telemetry"]["label_overflow"]
    assert overflow == {"t.by_tenant": 6}


@obs
def test_label_cardinality_guard_gauge_and_histogram():
    reg = MetricsRegistry()
    g = reg.gauge("t.depth_by", label="k", max_label_values=2)
    for k in range(5):
        g.set(float(k), label_value=f"k{k}")
    series = reg.render_json()["t"]["depth_by"]
    assert set(series) == {"k0", "k1", "other"}
    assert series["other"] == 4.0  # last overflow write wins (gauge)
    h = reg.histogram("t.lat_by", label="route", max_label_values=2)
    for k in range(5):
        h.observe(1.0, label_value=f"r{k}")
    hseries = h.collect()
    assert set(hseries) == {"r0", "r1", "other"}
    assert hseries["other"]["count"] == 3
    overflow = reg.render_json()["telemetry"]["label_overflow"]
    assert overflow == {"t.depth_by": 3, "t.lat_by": 3}


@obs
def test_label_guard_default_cap_is_64():
    reg = MetricsRegistry()
    c = reg.counter("t.default_cap", label="k")
    for k in range(70):
        c.inc(label_value=f"k{k:03d}")
    series = reg.render_json()["t"]["default_cap"]
    assert len(series) == 65  # 64 + "other"
    assert series["other"] == 6


@obs
def test_callback_backed_series_are_exempt_from_the_guard():
    # fn-backed instruments render whatever the producer owns — the
    # producer bounds its own state (shaping's tenant cap etc.)
    reg = MetricsRegistry()
    reg.gauge(
        "t.fn_backed",
        label="k",
        fn=lambda: {f"k{i}": i for i in range(80)},
    )
    assert len(reg.render_json()["t"]["fn_backed"]) == 80


# -- metric-name lint (CI wiring for tools/check_metric_names.py) -------------


@obs
def test_metric_name_lint():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_metric_names.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@obs
def test_metric_name_lint_catches_violations():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_metric_names import lint
    finally:
        sys.path.pop(0)

    errors = lint(
        [
            ("a.b", "counter", "x.py:1", False),
            ("a.b", "gauge", "y.py:2", False),  # duplicate
            ("nodots", "counter", "z.py:3", False),  # bad grammar
            ("c.d", "counter", "w.py:4", True),  # f-string
        ]
    )
    assert len(errors) == 3


# -- launch-recording lint (ISSUE 14 satellite) --------------------------------


@obs
def test_launch_recording_lint():
    """No module may mutate a launch-counter global directly (the
    pre-ISSUE-14 unlocked read-modify-write race), and every kernel
    seam must keep its recorder call + back-compat __getattr__."""
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO / "tools" / "check_launch_recording.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@obs
def test_launch_recording_lint_catches_violations():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_launch_recording import (
            lint_jit_bypass,
            lint_l0_family,
            lint_module,
            lint_seam,
        )
    finally:
        sys.path.pop(0)

    # a reintroduced module-global increment must fail
    errs = lint_module(
        "x.py",
        "N_LAUNCHES = 0\n"
        "def f():\n"
        "    global N_LAUNCHES\n"
        "    N_LAUNCHES += 1\n",
    )
    assert len(errs) == 3  # assign + global decl + aug-assign
    assert all("N_LAUNCHES" in e for e in errs)
    # the attribute-target variant must fail too: the read rides the
    # module's recorder property and the write plants a real attr
    # that shadows it (the plane_row_stats regression, ISSUE 15)
    errs = lint_module(
        "x.py",
        "from . import scatter_kernel as _sk\n"
        "def f():\n"
        "    _sk.N_DISPATCHES += 1\n",
    )
    assert len(errs) == 1 and "N_DISPATCHES" in errs[0]
    # a kernel seam that drops the recorder call or the __getattr__
    # property must fail both seam checks
    errs = lint_seam("y.py", "def run():\n    return 1\n")
    assert len(errs) == 2
    assert any("__getattr__" in e for e in errs)
    assert any("record_device_launch" in e for e in errs)
    # the compliant shape passes
    ok = lint_seam(
        "z.py",
        "def __getattr__(name):\n"
        "    raise AttributeError(name)\n"
        "def run():\n"
        "    from ..telemetry import record_device_launch\n"
        "    record_device_launch('fused', seam='kernel', tier=8,\n"
        "                         specs_real=1, specs_padded=8)\n",
    )
    assert ok == []
    # an L0 dispatch bypassing the recorded run_queries seam (a
    # direct jitted _query_batch call) must fail anywhere but the
    # seam module itself (ISSUE 15 satellite)
    src = "from .ops.kernel import _query_batch\n" \
          "def serve(arrays, enc):\n" \
          "    return _query_batch(arrays, enc, window_cap=1,\n" \
          "                        record_cap=1, n_iters=1)\n"
    errs = lint_jit_bypass("sbeacon_tpu/engine.py", src)
    assert len(errs) == 1 and "_query_batch" in errs[0]
    assert lint_jit_bypass("sbeacon_tpu/ops/kernel.py", src) == []
    # a dropped / re-pointed L0 family must fail
    errs = lint_l0_family(
        "class L0DeviceIndex:\n    flight_family = 'fused'\n",
        "DEVICE_FAMILIES = ('fused',)\n",
    )
    assert len(errs) == 2
    # quote style must not matter (the check is AST, not substring)...
    assert lint_l0_family(
        "class L0DeviceIndex:\n    flight_family = 'fused_l0'\n",
        "DEVICE_FAMILIES = ('fused', 'fused_l0')\n",
    ) == []
    # ...and a stray literal outside the tuple must not satisfy it
    errs = lint_l0_family(
        "class L0DeviceIndex:\n    flight_family = 'fused_l0'\n",
        'X = "fused_l0"\nDEVICE_FAMILIES = ("fused",)\n',
    )
    assert len(errs) == 1 and "DEVICE_FAMILIES" in errs[0]
    # the donated jit twin must stay behind the same door (ISSUE 17)
    errs = lint_jit_bypass(
        "sbeacon_tpu/engine.py",
        "from .ops.kernel import _query_batch_donated\n"
        "def serve(arrays, enc):\n"
        "    return _query_batch_donated(arrays, enc, window_cap=1,\n"
        "                                record_cap=1, n_iters=1)\n",
    )
    assert len(errs) == 1 and "_query_batch_donated" in errs[0]


# -- native decode seam lint (ISSUE 20 satellite) ------------------------------


@obs
def test_native_seam_lint():
    """The ingest plane keeps ONE native decode seam (native_slice_text
    routing inflate_range locally and inflate_buffer remotely), and
    every caller keeps its per-blob pure-Python fallback guard."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_native_seam.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@obs
def test_native_seam_lint_catches_violations():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_native_seam import lint
    finally:
        sys.path.pop(0)

    clean = {
        "seam_defined": True,
        "seam_entries": {"inflate_range", "inflate_buffer"},
        "decode_calls": [
            ("inflate_range", "ingest/pipeline.py:10", "native_slice_text", False),
            ("inflate_buffer", "ingest/pipeline.py:20", "native_slice_text", False),
            # the reference reader's guarded local fast path is allowed
            ("inflate_range", "genomics/bgzf.py:30", "read_range", True),
        ],
        "seam_calls": [("ingest/pipeline.py:40", True)],
    }
    assert lint(clean) == []

    # a stray remote-leg call outside the seam must fail even guarded
    stray = dict(clean)
    stray["decode_calls"] = clean["decode_calls"] + [
        ("inflate_buffer", "engine.py:5", "serve", True)
    ]
    errs = lint(stray)
    assert len(errs) == 1 and "inflate_buffer" in errs[0]

    # an unguarded reader fast path must fail (it IS the fallback plane)
    bare = dict(clean)
    bare["decode_calls"] = [
        c for c in clean["decode_calls"] if not c[1].startswith("genomics")
    ] + [("inflate_range", "genomics/bgzf.py:30", "read_range", False)]
    errs = lint(bare)
    assert len(errs) == 1 and "try/except" in errs[0]

    # a seam that dropped the remote leg must fail
    local_only = dict(clean)
    local_only["seam_entries"] = {"inflate_range"}
    errs = lint(local_only)
    assert len(errs) == 1 and "inflate_buffer" in errs[0]

    # an unguarded seam caller must fail
    unguarded = dict(clean)
    unguarded["seam_calls"] = [("ingest/pipeline.py:40", False)]
    errs = lint(unguarded)
    assert len(errs) == 1 and "fallback" in errs[0]

    # empty scans are errors, not passes
    dead = dict(clean)
    dead["decode_calls"] = []
    dead["seam_entries"] = set()
    assert len(lint(dead)) >= 2


@obs
def test_warmup_ladder_lint_catches_violations():
    """ISSUE 17 satellite: the warmup-ladder parity lint over a
    compile snapshot — an active-ladder rung with no warmup-phase
    compile must fail, for the batcher's families and for the engine's
    mesh program (warmed at the one batch it serves) alike."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_launch_recording import (
            expected_warm_rungs,
            lint_warmup_ladder,
        )
    finally:
        sys.path.pop(0)
    from sbeacon_tpu.ops.kernel import TierLadder

    def entry(family, tier, key, warmup=True):
        return {
            "key": key,
            "family": family,
            "tier": tier,
            "warmup": warmup,
        }

    # full coverage passes — snapshot-dict and bare-list forms alike
    snap = {
        "entries": [
            entry("fused", 8, "f:8"),
            entry("fused", 64, "f:64"),
            entry("mesh", 1, "m:1"),
        ]
    }
    expected = {"fused": (8, 64), "mesh": (1,)}
    assert lint_warmup_ladder(snap, expected) == []
    assert lint_warmup_ladder(snap["entries"], expected) == []
    # an uncovered rung fails, naming family and tier
    errs = lint_warmup_ladder(snap, {"fused": (8, 16, 64)})
    assert len(errs) == 1 and "fused" in errs[0] and "16" in errs[0]
    # a compile stamped OUTSIDE warmup does not count as coverage
    errs = lint_warmup_ladder(
        [entry("fused", 8, "f:8", warmup=False)], {"fused": (8,)}
    )
    assert len(errs) == 1 and "warmup" in errs[0]
    # a family's coverage is its own: another family's warm cell at
    # the same tier does not count
    errs = lint_warmup_ladder(
        [entry("fused", 1, "f:1")], {"mesh": (1,)}
    )
    assert len(errs) == 1 and "mesh" in errs[0]
    # the expected-map helper mirrors the warmup loops: every family
    # the batcher pads warms every serving rung
    lad = TierLadder((8, 16, 32, 64, 512, 2048))
    exp = expected_warm_rungs(lad, families=("fused", "fused_l0"))
    assert exp["fused"] == (8, 16, 32, 64, 512, 2048)
    assert exp["fused_l0"] == exp["fused"]


# -- annotation-key lint (ISSUE 11 satellite) ----------------------------------


@obs
def test_annotation_key_lint():
    """Every annotate(...) key under sbeacon_tpu/ must appear in the
    literal telemetry.ANNOTATION_KEYS registry, and every registered
    key must be used — two-way parity, like the metric catalogue."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_annotation_keys.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- probe-route lint (ISSUE 12 satellite) -------------------------------------


@obs
def test_probe_route_lint():
    """The SLO budget exclusion, the API probe-bypass path set, and
    the latency route-label set must all derive from the ONE literal
    source (slo.PROBE_ROUTE_LABELS) — static derivation checks in the
    subprocess, behavioural two-way parity in-process."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_probe_routes.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_probe_routes import runtime_parity
    finally:
        sys.path.pop(0)
    errors = runtime_parity()
    assert not errors, errors


@obs
def test_probe_route_lint_catches_violations(tmp_path):
    """A hand-maintained probe list in app.py must fail the lint."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_probe_routes import lint_app, lint_source
    finally:
        sys.path.pop(0)

    errors, labels, non_path = lint_source()
    assert not errors and labels and non_path <= labels
    # simulate the drift: a literal tuple of probe paths in app code
    import check_probe_routes as cpr

    bad = tmp_path / "app.py"
    bad.write_text(
        'PROBES = ("health", "ops/events")\n'
        "def handle(self, route):\n"
        "    return route in PROBES\n"
    )
    orig = cpr.APP_PY
    cpr.APP_PY = bad
    try:
        errs = cpr.lint_app(labels)
    finally:
        cpr.APP_PY = orig
    assert any("collection literal" in e for e in errs)


@obs
def test_annotation_key_lint_catches_violations():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_annotation_keys import lint as akl_lint
    finally:
        sys.path.pop(0)

    registry = {"tenant", "lane", "unused_key"}
    errors = akl_lint(
        {"tenant": ["a.py:1"], "bogus": ["b.py:2"]}, registry
    )
    # one unregistered key + one registered-but-unused x2 (lane too)
    assert any("bogus" in e for e in errors)
    assert any("unused_key" in e for e in errors)
    assert any("lane" in e for e in errors)
    assert akl_lint({"tenant": ["a.py:1"]}, None)  # missing registry
    assert akl_lint({}, registry)  # no call sites at all


# -- fault-seam lint (ISSUE 16 satellite) --------------------------------------


@obs
def test_fault_seam_lint():
    """Every fault_point() site in sbeacon_tpu/ must have a row in the
    DEPLOYMENT.md fault-plan table and vice versa — two-way parity, so
    a chaos plan can only name seams the code hits and the table stays
    the complete seam inventory."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_fault_seams.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@obs
def test_fault_seam_lint_catches_violations(tmp_path, monkeypatch):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_fault_seams as cfs
    finally:
        sys.path.pop(0)

    pkg = tmp_path / "sbeacon_tpu"
    (pkg / "harness").mkdir(parents=True)
    (pkg / "harness" / "faults.py").write_text(
        "def fault_point(site, detail=''):\n    pass\n"
    )
    (pkg / "mod.py").write_text(
        "from .harness.faults import fault_point\n"
        "def f(name):\n"
        "    fault_point('documented.site', 'd')\n"
        "    fault_point('rogue.site')\n"
        "    fault_point(name)\n"  # computed: unlintable
    )
    doc = tmp_path / "DEPLOYMENT.md"
    doc.write_text(
        "<!-- fault-plan:begin -->\n"
        "| Site | Where | detail |\n"
        "|---|---|---|\n"
        "| `documented.site` | mod.py | — |\n"
        "| `ghost.site` | nowhere | — |\n"
        "<!-- fault-plan:end -->\n"
    )
    monkeypatch.setattr(cfs, "REPO", tmp_path)
    monkeypatch.setattr(cfs, "PKG", pkg)
    monkeypatch.setattr(cfs, "DEPLOYMENT", doc)
    errors = cfs.lint()
    assert any("rogue.site" in e for e in errors)
    assert any("ghost.site" in e for e in errors)
    assert any("string literal" in e for e in errors)
    assert not any("documented.site" in e for e in errors)
