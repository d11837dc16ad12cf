"""Multi-tenant traffic shaping (ISSUE 8): tenant/lane classification,
weighted deficit-round-robin fair queues with per-tenant bounds,
adaptive Retry-After from the measured queue-wait ring, the SLO-driven
brownout ladder (hedge kill-switch -> bulk pause -> AIMD cap squeeze ->
global shed, with hysteresis), single-flight collapsing edge cases, and
the mixed-tenant overload acceptance: a bulk flood at a multiple of
capacity cannot starve the interactive tenant.
"""

import random
import threading
import time

import pytest

from sbeacon_tpu.harness import faults
from sbeacon_tpu.resilience import (
    Deadline,
    DeadlineExceeded,
    Overloaded,
    deadline_scope,
)
from sbeacon_tpu.shaping import (
    BROWNOUT_RUNGS,
    LANE_BULK,
    LANE_INTERACTIVE,
    BrownoutLadder,
    FairQueueAdmission,
    TrafficShaper,
    classify_lane,
    classify_tenant,
    parse_tenant_weights,
)
from sbeacon_tpu.telemetry import (
    RequestContext,
    annotate,
    journal,
    request_context,
)

shaping = pytest.mark.shaping


@pytest.fixture(autouse=True)
def _clean_process_globals():
    """The hedge kill-switch and fault injector are process-global —
    no test may leak them into its neighbors."""
    yield
    faults.uninstall()
    from sbeacon_tpu.parallel import dispatch

    dispatch.set_hedging_enabled(True)


# -- classification -----------------------------------------------------------


@shaping
def test_classify_tenant_header_key_anon():
    assert classify_tenant({"X-Beacon-Tenant": "gold"}) == "gold"
    # case-insensitive header lookup
    assert classify_tenant({"x-beacon-tenant": "free_1.a-b"}) == "free_1.a-b"
    # malformed header values never reach labels/journal verbatim
    k = classify_tenant(
        {"X-Beacon-Tenant": "bad\nvalue", "Authorization": "Bearer abc"}
    )
    assert k.startswith("key-") and len(k) == 12
    # the same credential buckets stably, different ones differently
    assert k == classify_tenant({"Authorization": "Bearer abc"})
    assert k != classify_tenant({"Authorization": "Bearer xyz"})
    assert classify_tenant({}) == "anon"
    assert classify_tenant(None) == "anon"


@shaping
def test_classify_lane():
    rec = {"query": {"requestedGranularity": "record"}}
    boo = {"query": {"requestedGranularity": "boolean"}}
    assert classify_lane("g_variants", None, rec) == LANE_BULK
    assert classify_lane("g_variants", None, boo) == LANE_INTERACTIVE
    assert classify_lane("g_variants", {"requestedGranularity": "record"},
                         None) == LANE_BULK
    assert classify_lane("individuals", None, {}) == LANE_INTERACTIVE
    assert classify_lane("info", None, None) == LANE_INTERACTIVE
    # bulk ingest rides the bulk lane regardless of body shape
    assert classify_lane("submit", None, {"datasetId": "x"}) == LANE_BULK


@shaping
def test_parse_tenant_weights():
    assert parse_tenant_weights("gold=4,free=1") == {
        "gold": 4.0, "free": 1.0,
    }
    assert parse_tenant_weights("") == {}
    with pytest.raises(ValueError):
        parse_tenant_weights("gold")
    with pytest.raises(ValueError):
        parse_tenant_weights("gold=0")
    with pytest.raises(ValueError):
        parse_tenant_weights("bad name=2")


# -- fair queue unit ----------------------------------------------------------


def _drain(q, tenants_threads):
    for t in tenants_threads:
        t.join(30)
        assert not t.is_alive(), "fair-queue waiter hung"


@shaping
def test_fast_path_admit_and_release():
    q = FairQueueAdmission(max_in_flight=2, tenant_max_in_flight=2)
    key = q.acquire("t1", LANE_INTERACTIVE)
    assert key == "t1"
    assert q.totals()["in_flight"] == 1
    q.release(key)
    assert q.totals()["in_flight"] == 0
    assert q.totals()["admitted"] == 1


@shaping
def test_wdrr_weighted_drain_ratio():
    """Weight 3 vs 1: a saturated drain grants 3 gold per free."""
    q = FairQueueAdmission(
        max_in_flight=1,
        tenant_max_in_flight=1,
        tenant_queue_depth=64,
        weights={"gold": 3.0, "free": 1.0},
    )
    seed = q.acquire("seed", LANE_INTERACTIVE)  # saturate capacity
    order: list[str] = []
    lock = threading.Lock()

    def waiter(tenant):
        key = q.acquire(tenant, LANE_INTERACTIVE)
        with lock:
            order.append(tenant)
        q.release(key)

    threads = []
    # alternate arrival so arrival order cannot explain the ratio
    for i in range(9):
        for tenant in ("gold", "free"):
            t = threading.Thread(target=waiter, args=(tenant,), daemon=True)
            n0 = q.totals()["queued"]
            t.start()
            threads.append(t)
            for _ in range(500):
                if q.totals()["queued"] > n0:
                    break
                time.sleep(0.002)
    q.release(seed)
    _drain(q, threads)
    assert len(order) == 18
    # over the contested prefix (both queues non-empty) the DRR grants
    # converge to the 3:1 weight ratio: 12 grants = 9 gold + 3 free
    assert order[:12].count("gold") == 9, order
    assert q.totals()["in_flight"] == 0


@shaping
def test_wdrr_fractional_weight_below_half_still_dispatches():
    """Regression: a tenant weight < 0.5 could never bank a full unit
    of deficit inside one dispatch pass (fixed 2n+1 visits), so its
    queued waiter was stranded — only freed by the queue-wait shed —
    even though the server sat free (work conservation broken)."""
    q = FairQueueAdmission(
        max_in_flight=1,
        tenant_max_in_flight=1,
        tenant_queue_depth=4,
        weights={"slow": 0.4},
        max_queue_wait_s=5.0,
    )
    seed = q.acquire("seed", LANE_INTERACTIVE)  # saturate capacity
    got: list[str] = []

    def waiter():
        key = q.acquire("slow", LANE_INTERACTIVE)
        got.append(key)
        q.release(key)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    for _ in range(500):
        if q.totals()["queued"] == 1:
            break
        time.sleep(0.002)
    t0 = time.monotonic()
    q.release(seed)  # the only dispatch trigger: must grant "slow" now
    t.join(5)
    assert not t.is_alive() and got == ["slow"]
    assert time.monotonic() - t0 < 1.0, "waiter freed by timeout, not DRR"
    assert q.totals()["shed"] == 0


@shaping
def test_shaper_close_restores_process_hedging():
    """An app discarded while browned out must hand the process-global
    hedge kill-switch back enabled — later apps (or other pools in the
    process) would otherwise silently run with hedging off forever."""
    from sbeacon_tpu.parallel.dispatch import (
        hedging_enabled,
        set_hedging_enabled,
    )

    q = FairQueueAdmission(max_in_flight=4)
    ladder = BrownoutLadder(
        q,
        up_hold_s=0.0,
        down_hold_s=0.0,
        hedge_control=set_hedging_enabled,
    )
    shaper = TrafficShaper(queue=q, ladder=ladder)
    ladder.on_signal(["g_variants"])
    assert ladder.level == 1 and not hedging_enabled()
    shaper.close()
    assert hedging_enabled()


@shaping
def test_per_tenant_cap_isolation_and_queue_full_shed():
    q = FairQueueAdmission(
        max_in_flight=10,
        tenant_max_in_flight=2,
        tenant_queue_depth=2,
        retry_floor_s=1.0,
    )
    k1 = q.acquire("x", LANE_INTERACTIVE)
    k2 = q.acquire("x", LANE_INTERACTIVE)
    threads = []
    for _ in range(2):  # fill x's interactive queue
        t = threading.Thread(
            target=lambda: q.release(q.acquire("x", LANE_INTERACTIVE)),
            daemon=True,
        )
        n0 = q.totals()["queued"]
        t.start()
        threads.append(t)
        for _ in range(500):
            if q.totals()["queued"] > n0:
                break
            time.sleep(0.002)
    # queue full: shed with the adaptive Retry-After (floor: no waits yet)
    with pytest.raises(Overloaded) as ei:
        q.acquire("x", LANE_INTERACTIVE)
    assert ei.value.status == 429
    assert ei.value.retry_after_s == 1.0
    # a saturated tenant never blocks another: y admits instantly
    ky = q.acquire("y", LANE_INTERACTIVE)
    q.release(ky)
    q.release(k1)
    q.release(k2)
    _drain(q, threads)
    shed = q.tenant_field("shed")
    assert shed["x"] == 1 and shed["y"] == 0


@shaping
def test_interactive_precedence_over_bulk():
    q = FairQueueAdmission(
        max_in_flight=1,
        tenant_max_in_flight=1,
        bulk_starvation_ms=60_000,  # no escape in this test
    )
    seed = q.acquire("seed", LANE_INTERACTIVE)
    order = []
    lock = threading.Lock()

    def waiter(tenant, lane):
        key = q.acquire(tenant, lane)
        with lock:
            order.append(lane)
        q.release(key)

    threads = []
    # the BULK waiter arrives FIRST, interactive after — precedence,
    # not arrival order, must decide
    for tenant, lane in (
        ("a", LANE_BULK), ("b", LANE_INTERACTIVE), ("c", LANE_INTERACTIVE),
    ):
        t = threading.Thread(target=waiter, args=(tenant, lane), daemon=True)
        n0 = q.totals()["queued"]
        t.start()
        threads.append(t)
        for _ in range(500):
            if q.totals()["queued"] > n0:
                break
            time.sleep(0.002)
    q.release(seed)
    _drain(q, threads)
    assert order == [LANE_INTERACTIVE, LANE_INTERACTIVE, LANE_BULK]


@shaping
def test_bulk_starvation_escape_hatch():
    clk = [0.0]
    q = FairQueueAdmission(
        max_in_flight=1,
        tenant_max_in_flight=1,
        bulk_starvation_ms=500.0,
        clock=lambda: clk[0],
    )
    seed = q.acquire("seed", LANE_INTERACTIVE)
    order = []
    lock = threading.Lock()

    def waiter(tenant, lane):
        key = q.acquire(tenant, lane)
        with lock:
            order.append(lane)
        q.release(key)

    threads = []
    for tenant, lane in (
        ("a", LANE_BULK), ("b", LANE_INTERACTIVE), ("c", LANE_INTERACTIVE),
    ):
        t = threading.Thread(target=waiter, args=(tenant, lane), daemon=True)
        n0 = q.totals()["queued"]
        t.start()
        threads.append(t)
        for _ in range(500):
            if q.totals()["queued"] > n0:
                break
            time.sleep(0.002)
    clk[0] = 1.0  # the bulk head is now 1000 ms old: past the threshold
    q.release(seed)
    _drain(q, threads)
    # the aged bulk waiter jumped the interactive lane — once
    assert order == [LANE_BULK, LANE_INTERACTIVE, LANE_INTERACTIVE]
    assert q.totals()["bulk_escapes"] == 1


@shaping
def test_adaptive_retry_after_reflects_measured_waits():
    clk = [0.0]
    q = FairQueueAdmission(
        max_in_flight=1,
        tenant_max_in_flight=1,
        retry_floor_s=1.0,
        retry_ceil_s=3.0,
        clock=lambda: clk[0],
    )
    # no measurements yet: the floor
    assert q.retry_after(LANE_INTERACTIVE) == 1.0
    seed = q.acquire("seed", LANE_INTERACTIVE)
    done = []
    t = threading.Thread(
        target=lambda: done.append(q.acquire("t", LANE_INTERACTIVE)),
        daemon=True,
    )
    t.start()
    for _ in range(500):
        if q.totals()["queued"] == 1:
            break
        time.sleep(0.002)
    clk[0] = 2.0  # the waiter measurably waited 2 s
    q.release(seed)
    t.join(10)
    assert done == ["t"]
    assert q.retry_after(LANE_INTERACTIVE) == 2.0
    # the ceiling clamps a pathological backlog
    q.release("t")
    seed = q.acquire("seed", LANE_INTERACTIVE)
    t = threading.Thread(
        target=lambda: q.release(q.acquire("t", LANE_INTERACTIVE)),
        daemon=True,
    )
    t.start()
    for _ in range(500):
        if q.totals()["queued"] == 1:
            break
        time.sleep(0.002)
    clk[0] = 120.0
    q.release(seed)
    t.join(10)
    assert q.retry_after(LANE_INTERACTIVE) == 3.0


@shaping
def test_queue_wait_bounded_by_request_deadline():
    q = FairQueueAdmission(max_in_flight=1, tenant_max_in_flight=1)
    seed = q.acquire("seed", LANE_INTERACTIVE)
    t0 = time.perf_counter()
    with deadline_scope(Deadline.after(0.2)):
        with pytest.raises(DeadlineExceeded):
            q.acquire("t", LANE_INTERACTIVE)
    assert time.perf_counter() - t0 < 2.0
    assert q.totals()["queued"] == 0  # the waiter withdrew
    q.release(seed)


@shaping
def test_max_tenants_overflow_bucket():
    q = FairQueueAdmission(max_in_flight=8, max_tenants=2)
    assert q.acquire("t1", LANE_INTERACTIVE) == "t1"
    assert q.acquire("t2", LANE_INTERACTIVE) == "t2"
    # tenant table full: new ids share (and are capped as) one bucket
    assert q.acquire("t3", LANE_INTERACTIVE) == "overflow"
    assert q.acquire("t4", LANE_INTERACTIVE) == "overflow"
    assert q.tenants()["overflow"]["inFlight"] == 2
    for key in ("t1", "t2", "overflow", "overflow"):
        q.release(key)


@shaping
def test_brownout_bulk_pause_flushes_queued_bulk():
    q = FairQueueAdmission(max_in_flight=1, tenant_max_in_flight=1)
    seed = q.acquire("seed", LANE_INTERACTIVE)
    errs = []

    def bulk_waiter():
        try:
            q.release(q.acquire("t", LANE_BULK))
        except Overloaded as e:
            errs.append(e)

    t = threading.Thread(target=bulk_waiter, daemon=True)
    t.start()
    for _ in range(500):
        if q.totals()["queued"] == 1:
            break
        time.sleep(0.002)
    q.set_brownout(bulk_paused=True)
    t.join(5)
    assert not t.is_alive() and len(errs) == 1  # shed NOW, not at timeout
    # and new bulk arrivals shed immediately while paused
    with pytest.raises(Overloaded):
        q.acquire("t", LANE_BULK)
    # interactive is untouched
    q.set_brownout(bulk_paused=False)
    q.release(seed)
    q.release(q.acquire("t", LANE_BULK))


# -- brownout ladder unit -----------------------------------------------------


@shaping
def test_brownout_ladder_up_down_with_aimd_and_hysteresis():
    clk = [0.0]
    q = FairQueueAdmission(max_in_flight=4)
    flags = []
    ladder = BrownoutLadder(
        q,
        up_hold_s=1.0,
        down_hold_s=2.0,
        md_factor=0.5,
        ai_step=0.25,
        min_scale=0.125,
        hedge_control=flags.append,
        clock=lambda: clk[0],
    )
    seq0 = journal.last_seq()
    ladder.on_signal(["g_variants"])  # breach starts: no step yet (hold)
    assert ladder.level == 0
    levels = []
    for step in range(1, 8):
        clk[0] = float(step)
        ladder.on_signal(["g_variants"])
        levels.append((ladder.level, ladder.cap_scale))
    # hedge off -> bulk pause -> cap squeeze (0.5 -> 0.25 -> 0.125) ->
    # global shed; then saturated (no further step)
    assert levels == [
        (1, 1.0), (2, 1.0), (3, 0.5), (3, 0.25), (3, 0.125),
        (4, 0.125), (4, 0.125),
    ]
    assert flags[0] is False  # hedging killed at rung 1
    tot = q.totals()
    assert tot["bulk_paused"] and tot["global_shed"]
    assert tot["cap_scale"] == 0.125

    # recovery: sustained-clear steps down, restoring the cap
    # additively BEFORE leaving the squeeze rung (AIMD)
    clk[0] = 10.0
    ladder.on_signal([])  # clear starts: hysteresis hold
    assert ladder.level == 4
    down = []
    for step in range(6):
        clk[0] = 12.0 + 2.0 * step
        ladder.on_signal([])
        down.append((ladder.level, ladder.cap_scale))
    assert down[0] == (3, 0.125)  # global shed lifted first
    assert down[-1][1] == 1.0  # cap fully restored
    while ladder.level > 0:
        clk[0] += 2.0
        ladder.on_signal([])
    assert flags[-1] is True  # hedging re-enabled
    tot = q.totals()
    assert not tot["bulk_paused"] and not tot["global_shed"]
    evs = journal.events(since=seq0, kind="shaping.brownout", limit=64)
    dirs = {e["data"]["direction"] for e in evs}
    assert dirs == {"up", "down"}
    assert {e["data"]["rung"] for e in evs} >= set(BROWNOUT_RUNGS)


# -- app-level ---------------------------------------------------------------


def _records(seed=5, n=300):
    from sbeacon_tpu.testing import random_records

    rng = random.Random(seed)
    return random_records(rng, chrom="21", n=n, n_samples=2)


def _shard(recs):
    from sbeacon_tpu.index.columnar import build_index

    return build_index(
        recs,
        dataset_id="sh",
        vcf_location="synthetic://sh",
        sample_names=["A", "B"],
    )


def _register_dataset(app):
    app.store.upsert(
        "datasets",
        [
            {
                "id": "sh",
                "name": "sh",
                "_assemblyId": "GRCh38",
                "_vcfLocations": ["synthetic://sh"],
            }
        ],
    )


def _gv_query(rec, k=0, granularity="boolean"):
    return {
        "query": {
            "requestedGranularity": granularity,
            "requestParameters": {
                "assemblyId": "GRCh38",
                "referenceName": "21",
                "start": [max(0, rec.pos - 1 - k)],
                "end": [rec.pos + len(rec.ref) + 5 + k],
                "alternateBases": "N",
            },
        }
    }


def _app(tmp_path, *, shaping_cfg=None, resilience_cfg=None, obs_cfg=None):
    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.config import (
        BeaconConfig,
        EngineConfig,
        ObservabilityConfig,
        ResilienceConfig,
        ShapingConfig,
        StorageConfig,
    )

    cfg = BeaconConfig(
        storage=StorageConfig(root=tmp_path / "d"),
        engine=EngineConfig(use_mesh=False, microbatch=True),
        shaping=shaping_cfg or ShapingConfig(),
        resilience=resilience_cfg or ResilienceConfig(),
        observability=obs_cfg or ObservabilityConfig(),
    )
    cfg.storage.ensure()
    app = BeaconApp(cfg)
    recs = _records()
    app.engine.add_index(_shard(recs))
    _register_dataset(app)
    return app, recs


@shaping
def test_retry_after_header_equals_envelope_over_http(tmp_path):
    """The satellite regression: the Retry-After header and the
    envelope's retryAfterSeconds carry the SAME whole-seconds value
    (the header used to round up what the envelope kept fractional)."""
    import http.client
    import json as json_mod

    from sbeacon_tpu.api.server import start_background
    from sbeacon_tpu.config import ShapingConfig

    app, recs = _app(
        tmp_path,
        shaping_cfg=ShapingConfig(
            tenant_max_in_flight=1,
            tenant_queue_depth=1,
            max_queue_wait_s=5.0,
            retry_after_floor_s=3.0,  # sub-second-incapable on the wire
            brownout=False,
        ),
    )
    started = threading.Event()
    release = threading.Event()
    orig = app.engine.search

    def gated(pl):
        started.set()
        release.wait(10)
        return orig(pl)

    app.engine.search = gated
    server, _t = start_background(app)
    port = server.server_address[1]
    try:
        hold = threading.Thread(
            target=lambda: app.handle(
                "POST",
                "/g_variants",
                body=_gv_query(recs[0]),
                headers={"X-Beacon-Tenant": "t1"},
            ),
            daemon=True,
        )
        hold.start()
        assert started.wait(10)
        queued = threading.Thread(
            target=lambda: app.handle(
                "POST",
                "/g_variants",
                body=_gv_query(recs[1]),
                headers={"X-Beacon-Tenant": "t1"},
            ),
            daemon=True,
        )
        queued.start()
        for _ in range(500):
            if app.shaping.queue.totals()["queued"] == 1:
                break
            time.sleep(0.002)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request(
            "POST",
            "/g_variants",
            body=json_mod.dumps(_gv_query(recs[2])).encode(),
            headers={
                "Content-Type": "application/json",
                "X-Beacon-Tenant": "t1",
            },
        )
        r = conn.getresponse()
        body = json_mod.loads(r.read())
        conn.close()
        assert r.status == 429, body
        assert body["retryAfterSeconds"] == 3
        assert isinstance(body["retryAfterSeconds"], int)
        assert r.getheader("Retry-After") == str(body["retryAfterSeconds"])
        release.set()
        hold.join(15)
        queued.join(15)
    finally:
        release.set()
        server.shutdown()
        app.close()


@shaping
def test_admission_queue_fault_site(tmp_path):
    """Chaos plans can fail/delay the fair-queue path, targeted by
    tenant via the rule's ``match`` on the ``tenant:lane`` detail."""
    app, recs = _app(tmp_path)
    try:
        faults.install(
            {
                "seed": 3,
                "rules": [
                    {
                        "site": "admission.queue",
                        "kind": "error",
                        "match": "chaos:",
                    }
                ],
            }
        )
        status, body = app.handle(
            "GET", "/info", headers={"X-Beacon-Tenant": "chaos"}
        )
        assert status == 500 and "error" in body
        # other tenants untouched
        status, _ = app.handle(
            "GET", "/info", headers={"X-Beacon-Tenant": "calm"}
        )
        assert status == 200
        faults.install(
            {
                "seed": 3,
                "rules": [
                    {
                        "site": "admission.queue",
                        "kind": "latency",
                        "ms": 300.0,
                        "match": "chaos:",
                    }
                ],
            }
        )
        t0 = time.perf_counter()
        status, _ = app.handle(
            "GET", "/info", headers={"X-Beacon-Tenant": "chaos"}
        )
        assert status == 200
        assert time.perf_counter() - t0 >= 0.29
    finally:
        app.close()


# -- single-flight collapsing -------------------------------------------------


@shaping
def test_single_flight_n_identical_queries_one_search(tmp_path):
    """Acceptance: N identical concurrent cold queries execute exactly
    ONE engine search — followers attach to the leader's pending
    result (asserted via the search/launch counters)."""
    app, recs = _app(tmp_path)
    app.handle("POST", "/g_variants", body=_gv_query(recs[5]))  # warm
    calls = [0]
    lock = threading.Lock()
    orig = app.engine.search

    def counting(pl):
        with lock:
            calls[0] += 1
        time.sleep(0.3)  # hold the flight open so followers coalesce
        return orig(pl)

    app.engine.search = counting
    occ0 = app.engine._batcher.occupancy()["launches"]
    body = _gv_query(recs[0])
    results = []

    def client():
        results.append(app.handle("POST", "/g_variants", body=body))

    threads = [
        threading.Thread(target=client, daemon=True) for _ in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    try:
        assert calls[0] == 1, f"{calls[0]} engine searches for 6 clients"
        assert all(s == 200 for s, _ in results)
        exists = {b["responseSummary"]["exists"] for _, b in results}
        assert len(exists) == 1  # every waiter got the leader's answer
        assert (
            app.engine._batcher.occupancy()["launches"] - occ0 <= 1
        )
        assert app.query_runner.metrics()["coalesced"] >= 1
    finally:
        app.close()


@shaping
def test_single_flight_leader_deadline_expires_followers_fall_back(
    tmp_path,
):
    """The leader's deadline lapses mid-flight: the leader answers 504,
    the job is abandoned (never cached as empty), and a follower with
    its own longer deadline falls back to a direct search and gets the
    real answer."""
    app, recs = _app(tmp_path)
    app.handle("POST", "/g_variants", body=_gv_query(recs[5]))  # warm
    orig = app.engine.search
    started = threading.Event()
    first = [True]

    def slow_once(pl):
        if first[0]:
            first[0] = False
            started.set()
            time.sleep(0.8)  # outlives the leader's 0.3 s deadline
        return orig(pl)

    app.engine.search = slow_once
    body = _gv_query(recs[0])
    out = {}

    def leader():
        out["leader"] = app.handle(
            "POST",
            "/g_variants",
            body=body,
            headers={"X-Beacon-Deadline": "0.3"},
        )

    def follower():
        started.wait(10)
        out["follower"] = app.handle("POST", "/g_variants", body=body)

    ts = [
        threading.Thread(target=leader, daemon=True),
        threading.Thread(target=follower, daemon=True),
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive()
    try:
        assert out["leader"][0] == 504, out["leader"][1]
        assert out["follower"][0] == 200, out["follower"][1]
        assert "responseSummary" in out["follower"][1]
    finally:
        app.close()


@shaping
def test_single_flight_follower_deadline_shorter_than_leaders(tmp_path):
    """A follower whose own deadline is tighter than the leader's gives
    up with 504 while the leader's flight completes and answers 200."""
    app, recs = _app(tmp_path)
    app.handle("POST", "/g_variants", body=_gv_query(recs[5]))  # warm
    orig = app.engine.search
    started = threading.Event()
    first = [True]

    def slow_once(pl):
        if first[0]:
            first[0] = False
            started.set()
            time.sleep(0.6)
        return orig(pl)

    app.engine.search = slow_once
    body = _gv_query(recs[0])
    out = {}

    def leader():
        out["leader"] = app.handle("POST", "/g_variants", body=body)

    def follower():
        started.wait(10)
        out["follower"] = app.handle(
            "POST",
            "/g_variants",
            body=body,
            headers={"X-Beacon-Deadline": "0.2"},
        )

    ts = [
        threading.Thread(target=leader, daemon=True),
        threading.Thread(target=follower, daemon=True),
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive()
    try:
        assert out["follower"][0] == 504, out["follower"][1]
        assert out["leader"][0] == 200, out["leader"][1]
    finally:
        app.close()


@shaping
def test_single_flight_partial_marking_replays_onto_each_waiter(tmp_path):
    """A collapsed PARTIAL answer (replicas down) must mark EVERY
    waiter's envelope, not just the submitter's — the PR 6 handoff
    replay, exercised through the coalescing path."""
    app, recs = _app(tmp_path)
    app.handle("POST", "/g_variants", body=_gv_query(recs[5]))  # warm
    orig = app.engine.search
    started = threading.Event()
    first = [True]

    def partial_once(pl):
        responses = orig(pl)
        if first[0]:
            first[0] = False
            annotate(unavailable_datasets=("ghost-ds",))
            started.set()
            time.sleep(0.4)  # keep the flight open for the follower
        return responses

    app.engine.search = partial_once
    body = _gv_query(recs[0])
    out = {}

    def leader():
        out["leader"] = app.handle("POST", "/g_variants", body=body)

    def follower():
        started.wait(10)
        out["follower"] = app.handle("POST", "/g_variants", body=body)

    ts = [
        threading.Thread(target=leader, daemon=True),
        threading.Thread(target=follower, daemon=True),
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive()
    try:
        for who in ("leader", "follower"):
            status, doc = out[who]
            assert status == 200, (who, doc)
            assert doc["meta"]["unavailableDatasets"] == ["ghost-ds"], who
            assert any(
                "partial" in w for w in doc["meta"]["warnings"]
            ), who
    finally:
        app.close()


# -- runner lane-aware admission ----------------------------------------------


@shaping
def test_runner_bulk_lane_cap():
    """Bulk submissions may hold at most the configured share of the
    runner's pending slots; interactive submissions keep the rest."""
    from sbeacon_tpu.payloads import VariantQueryPayload
    from sbeacon_tpu.query_jobs import AsyncQueryRunner, QueryJobTable

    release = threading.Event()

    class StubEngine:
        config = None

        def search(self, payload):
            release.wait(10)
            return []

    runner = AsyncQueryRunner(
        StubEngine(), QueryJobTable(), workers=4, max_pending=4
    )
    assert runner._bulk_cap == 2

    def pl(k):
        return VariantQueryPayload(
            dataset_ids=["d"], reference_name="1", start_min=k, start_max=k
        )

    try:
        with request_context(RequestContext()):
            annotate(lane="bulk")
            runner.submit(pl(1))
            runner.submit(pl(2))
            with pytest.raises(Overloaded):
                runner.submit(pl(3))  # bulk share exhausted
            # interactive still admits into the remaining slots
            annotate(lane="interactive")
            runner.submit(pl(4))
        release.set()
        for _ in range(500):
            if runner.metrics()["bulk_active"] == 0:
                break
            time.sleep(0.01)
        assert runner.metrics()["bulk_active"] == 0  # slots released
    finally:
        release.set()
        runner.close()


# -- brownout through the app -------------------------------------------------


@shaping
def test_brownout_ladder_steps_up_in_app_and_recovers(tmp_path):
    """A seeded SLO breach (kernel-launch faults -> 5xx burn on both
    windows) steps the ladder up; every transition is visible at
    /ops/events and as the shaping.brownout_level gauge; a sustained
    recovery signal steps back down and re-enables hedging."""
    from sbeacon_tpu.config import ShapingConfig
    from sbeacon_tpu.parallel.dispatch import hedging_enabled

    app, recs = _app(
        tmp_path,
        shaping_cfg=ShapingConfig(
            brownout_up_hold_s=0.0, brownout_down_hold_s=0.0
        ),
    )
    app.slo.NOTIFY_INTERVAL_S = 0.0  # evaluate every request (test only)
    seq0 = journal.last_seq()
    try:
        app.handle("POST", "/g_variants", body=_gv_query(recs[5]))  # warm
        faults.install(
            {
                "seed": 7,
                "rules": [{"site": "kernel.launch", "kind": "error"}],
            }
        )
        statuses = []
        for k in range(8):
            s, _b = app.handle(
                "POST", "/g_variants", body=_gv_query(recs[k], k=k)
            )
            statuses.append(s)
        faults.uninstall()
        assert app.shaping.ladder.level == 4, statuses
        assert not hedging_enabled()
        # at global shed, new work answers 429 with Retry-After
        s, b = app.handle("POST", "/g_variants", body=_gv_query(recs[9]))
        assert s == 429 and b["retryAfterSeconds"] >= 1
        _, m = app.handle("GET", "/metrics")
        assert m["shaping"]["brownout_level"] == 4
        ups = journal.events(
            since=seq0, kind="shaping.brownout", limit=64
        )
        assert [e["data"]["level"] for e in ups if
                e["data"]["direction"] == "up"] == [1, 2, 3, 3, 3, 4]
        _, ev_doc = app.handle(
            "GET", "/ops/events", {"kind": "shaping.brownout"}
        )
        assert len(ev_doc["events"]) >= 6

        # recovery: the breach signal clears (direct ladder feed — the
        # SLO windows hold real minutes of history) and the ladder
        # walks back down, restoring caps and hedging
        for _ in range(30):
            app.shaping.ladder.on_signal([])
            if app.shaping.ladder.level == 0 and (
                app.shaping.ladder.cap_scale == 1.0
            ):
                break
            time.sleep(0.01)
        assert app.shaping.ladder.level == 0
        assert app.shaping.ladder.cap_scale == 1.0
        assert hedging_enabled()
        s, _b = app.handle("POST", "/g_variants", body=_gv_query(recs[9]))
        assert s == 200
        downs = [
            e
            for e in journal.events(
                since=seq0, kind="shaping.brownout", limit=128
            )
            if e["data"]["direction"] == "down"
        ]
        assert downs and downs[-1]["data"]["level"] == 0
    finally:
        app.close()


# -- the mixed-tenant overload acceptance -------------------------------------


@shaping
def test_mixed_tenant_overload_interactive_protected(tmp_path):
    """One tenant floods bulk (record) queries at several times
    capacity; the interactive tenant's fast-lane queries see ZERO 429s
    and keep p99 within 2x the unloaded p99, while the flooding tenant
    is shed with adaptive Retry-After values that reflect measured
    queue wait — not the 1.0 s constant."""
    from sbeacon_tpu.config import ResilienceConfig, ShapingConfig

    app, recs = _app(
        tmp_path,
        shaping_cfg=ShapingConfig(
            tenant_max_in_flight=1,
            tenant_queue_depth=3,
            max_queue_wait_s=2.5,
            bulk_starvation_ms=200.0,
            retry_after_floor_s=1.0,
            brownout=False,  # isolate fair queueing from the ladder
        ),
        resilience_cfg=ResilienceConfig(max_in_flight=8),
    )
    orig = app.engine.search

    def slow_bulk(pl):
        if pl.requested_granularity == "record":
            time.sleep(0.5)  # a heavyweight retrieval
        return orig(pl)

    app.engine.search = slow_bulk
    gold = {"X-Beacon-Tenant": "gold"}
    flood_hdr = {"X-Beacon-Tenant": "flood"}
    try:
        # warm the kernel path, then measure the unloaded baseline
        for k in range(5):
            app.handle("POST", "/g_variants", body=_gv_query(recs[k]),
                       headers=gold)
        unloaded = []
        for k in range(30):
            t0 = time.perf_counter()
            s, _b = app.handle(
                "POST", "/g_variants",
                body=_gv_query(recs[30 + k]), headers=gold,
            )
            unloaded.append(time.perf_counter() - t0)
            assert s == 200
        unloaded.sort()
        p99_unloaded = unloaded[int(0.99 * (len(unloaded) - 1))]

        stop = threading.Event()
        flood_stats = {"shed": 0, "ok": 0, "retry_after": []}
        flock = threading.Lock()

        def flooder(fid):
            k = 0
            while not stop.is_set():
                k += 1
                s, b = app.handle(
                    "POST",
                    "/g_variants",
                    body=_gv_query(
                        recs[(fid * 97 + k) % len(recs)],
                        k=fid * 131 + k,
                        granularity="record",
                    ),
                    headers=flood_hdr,
                )
                shed = s == 429
                with flock:
                    if shed:
                        flood_stats["shed"] += 1
                        flood_stats["retry_after"].append(
                            b["retryAfterSeconds"]
                        )
                    elif s == 200:
                        flood_stats["ok"] += 1
                if shed:
                    # a token nod to the backoff advice (the real value
                    # would idle the flood entirely): without it the
                    # spin loop is pure GIL churn that bills scheduler
                    # noise to the interactive tenant's clock
                    time.sleep(0.05)

        flooders = [
            threading.Thread(target=flooder, args=(i,), daemon=True)
            for i in range(6)
        ]
        for t in flooders:
            t.start()
        # let the bulk queue reach steady state: with service time
        # 0.5 s and depth 3, granted waiters measure ~1.5 s waits, so
        # the adaptive Retry-After demonstrably exceeds the 1 s floor
        time.sleep(2.2)

        loaded, gold_429 = [], 0
        for k in range(30):
            t0 = time.perf_counter()
            s, _b = app.handle(
                "POST", "/g_variants",
                body=_gv_query(recs[90 + k]), headers=gold,
            )
            loaded.append(time.perf_counter() - t0)
            if s == 429:
                gold_429 += 1
        time.sleep(0.5)  # trailing sheds sample the steady-state ring
        stop.set()
        for t in flooders:
            t.join(30)
            assert not t.is_alive()

        loaded.sort()
        p99_loaded = loaded[int(0.99 * (len(loaded) - 1))]
        # the interactive tenant never sheds and keeps its latency: the
        # 50 ms floor absorbs CI scheduler noise at sub-ms baselines
        assert gold_429 == 0
        assert p99_loaded <= 2 * max(p99_unloaded, 0.05), (
            p99_loaded, p99_unloaded,
        )
        # the flooding tenant was shed, with backoff advice derived
        # from the measured queue wait (whole seconds > the 1 s
        # constant once the ring holds second-scale waits)
        assert flood_stats["shed"] > 0
        assert flood_stats["ok"] > 0  # shaped, not starved outright
        assert max(flood_stats["retry_after"]) >= 2, flood_stats
        shed_by_tenant = app.shaping.queue.tenant_field("shed")
        assert shed_by_tenant.get("flood", 0) == flood_stats["shed"]
        assert shed_by_tenant.get("gold", 0) == 0
    finally:
        app.close()


# -- lane-ordered micro-batcher pop -------------------------------------------


@shaping
def test_batcher_pops_interactive_lane_first():
    """When the accumulator backlog spans both lanes and exceeds one
    batch, interactive entries ride earlier launches than bulk ones
    (stable within a lane)."""
    from sbeacon_tpu.resilience import NO_DEADLINE
    from sbeacon_tpu.serving import MicroBatcher, _Pending

    b = MicroBatcher(max_batch=2, max_wait_ms=0.0, default_timeout_s=5.0)
    dindex = type("D", (), {})()  # weakref-able accumulator key
    acc = b._accum(dindex, (1, 1))
    order: list[tuple[str, int]] = []

    def fake_run(acc_, batch, dindex_, w, r):
        for p in batch:
            order.append((p.lane, p.specs[0]))
            p.result = "ok"
            p.event.set()
        # a launch arrives with its fetch-pipeline slot and gives it back
        acc_.pipeline.release()

    b._run_batch = fake_run
    lanes = ["bulk", "bulk", "interactive", "interactive", "bulk",
             "interactive"]
    with acc.lock:
        for i, lane in enumerate(lanes):
            acc.items.append(
                _Pending(
                    specs=[i],
                    event=threading.Event(),
                    lane=lane,
                    t_submit=time.perf_counter(),
                )
            )
        acc.leader_active = True
    b._serve(acc, dindex, 1, 1, None, NO_DEADLINE)
    assert [lane for lane, _i in order] == [
        "interactive"] * 3 + ["bulk"] * 3
    # stable within each lane: FIFO order survives the reorder
    assert [i for lane, i in order] == [2, 3, 5, 0, 1, 4]
    b.close()


@shaping
def test_batcher_aged_bulk_entry_keeps_fifo_spot():
    """Lane precedence must not become starvation: a bulk entry older
    than BULK_SORT_STARVATION_MS is exempt from being re-sorted behind
    interactive entries that arrived after it (a steady interactive
    stream re-sorts the tail on every pop and could otherwise displace
    an admitted bulk entry until its deadline)."""
    from sbeacon_tpu.resilience import NO_DEADLINE
    from sbeacon_tpu.serving import MicroBatcher, _Pending

    b = MicroBatcher(max_batch=2, max_wait_ms=0.0, default_timeout_s=5.0)
    dindex = type("D", (), {})()
    acc = b._accum(dindex, (1, 1))
    order: list[tuple[str, int]] = []

    def fake_run(acc_, batch, dindex_, w, r):
        for p in batch:
            order.append((p.lane, p.specs[0]))
            p.result = "ok"
            p.event.set()
        # a launch arrives with its fetch-pipeline slot and gives it back
        acc_.pipeline.release()

    b._run_batch = fake_run
    now = time.perf_counter()
    aged = now - b.BULK_SORT_STARVATION_MS / 1e3 - 1.0
    entries = [("bulk", aged), ("interactive", now), ("bulk", now),
               ("interactive", now)]
    with acc.lock:
        for i, (lane, ts) in enumerate(entries):
            acc.items.append(
                _Pending(
                    specs=[i],
                    event=threading.Event(),
                    lane=lane,
                    t_submit=ts,
                )
            )
        acc.leader_active = True
    b._serve(acc, dindex, 1, 1, None, NO_DEADLINE)
    # the aged bulk entry keeps its FIFO spot; the fresh one still
    # yields to the interactive lane
    assert order == [
        ("bulk", 0),
        ("interactive", 1),
        ("interactive", 3),
        ("bulk", 2),
    ], order
    b.close()


@shaping
def test_submit_reads_lane_from_ambient_context(tmp_path):
    """The API layer's lane note rides the request context into the
    batcher's _Pending entries."""
    from sbeacon_tpu.serving import MicroBatcher

    captured = {}
    orig_submit_many = MicroBatcher.submit_many

    app, recs = _app(tmp_path)

    def spy(self, dindex, specs, **kw):
        res = orig_submit_many(self, dindex, specs, **kw)
        ctx_lane = None
        from sbeacon_tpu.telemetry import current_context

        ctx = current_context()
        if ctx is not None:
            ctx_lane = ctx.notes.get("lane")
        captured.setdefault("lanes", []).append(ctx_lane)
        return res

    MicroBatcher.submit_many = spy
    try:
        s, _ = app.handle(
            "POST", "/g_variants",
            body=_gv_query(recs[0], granularity="record"),
        )
        assert s == 200
        assert "bulk" in captured["lanes"]
    finally:
        MicroBatcher.submit_many = orig_submit_many
        app.close()
