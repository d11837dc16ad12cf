"""Execution-plan plane (ISSUE 19): per-request plan documents
(plan.plan_stage / ``meta.executionPlan``), the sampled ``/ops/plans``
aggregate, the plan-drift sentinel, the ``?explain=1`` trust gate and
the ``tools/check_plan_stages.py`` static lint."""

import dataclasses
import json
import random
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from sbeacon_tpu.config import (
    AuthConfig,
    BeaconConfig,
    EngineConfig,
    ObservabilityConfig,
)
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.plan import (
    EXEMPLAR_KEEP,
    MAX_PLAN_SHAPES,
    MAX_PLAN_STAGES,
    PLAN_REASONS,
    PLAN_STAGES,
    VOLATILE_STAGES,
    PlanStore,
    plan_document,
    plan_note,
    plan_shape,
    plan_stage,
)
from sbeacon_tpu.telemetry import (
    RequestContext,
    journal,
    request_context,
)
from sbeacon_tpu.testing import random_records
from sbeacon_tpu.utils.trace import tracer

obs = pytest.mark.obs

REPO = Path(__file__).resolve().parent.parent

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="mesh path needs >=2 devices"
)

#: golden key set of the GET /ops/plans document
PLANS_KEYS = {
    "sampleN",
    "windowS",
    "driftWindows",
    "windowsRolled",
    "observations",
    "sampled",
    "shapes",
    "drifts",
}

#: golden key set of one meta.executionPlan document
EXECUTION_PLAN_KEYS = {"stages", "shape", "truncated"}


# -- producer hook + fingerprint (unit) ----------------------------------------


@obs
def test_plan_stage_is_noop_off_request_and_bounded():
    # off-request: must not raise, must not record anywhere
    plan_stage("cache", decision="hit")
    ctx = RequestContext(route="g_variants")
    with request_context(ctx):
        plan_stage(
            "cache",
            decision="hit",
            n=3,
            frac=0.5,
            flag=True,
            s="x" * 300,
            dropped_none=None,
            dropped_obj={"a": 1},
        )
    assert len(ctx.plan) == 1
    entry = ctx.plan[0]
    assert entry["stage"] == "cache" and entry["decision"] == "hit"
    detail = entry["detail"]
    # scalars kept, strings capped, None/containers dropped
    assert detail["n"] == 3 and detail["frac"] == 0.5
    assert detail["flag"] is True and len(detail["s"]) == 120
    assert "dropped_none" not in detail and "dropped_obj" not in detail
    # the stage list truncates instead of growing without bound
    with request_context(ctx):
        for i in range(MAX_PLAN_STAGES + 5):
            plan_stage("tier", decision=str(i))
    assert len(ctx.plan) == MAX_PLAN_STAGES
    doc = plan_document(ctx)
    assert set(doc) == EXECUTION_PLAN_KEYS
    assert doc["truncated"] is True


@obs
def test_plan_shape_excludes_volatile_stages():
    """Worker legs record from scatter-pool threads in arrival order
    and hedges fire on timing — they are evidence, not identity, so
    the fingerprint must not include them (they would fake drift)."""
    assert VOLATILE_STAGES <= PLAN_STAGES
    entries = [
        {"stage": "cache", "decision": "miss"},
        {"stage": "tier", "decision": "http"},
        {"stage": "worker", "decision": "hedged"},
        {
            "stage": "worker",
            "decision": "fast_fail",
            "reason": "breaker_open",
        },
        {"stage": "batch", "decision": "ShardIndex"},
        {"stage": "fallback", "decision": "partial", "reason": "no_replica"},
    ]
    shape = plan_shape(entries)
    assert shape == "cache=miss>tier=http>fallback=partial!no_replica"
    assert "worker" not in shape and "batch" not in shape
    # reordering only the volatile legs leaves the fingerprint stable
    swapped = [entries[0], entries[1], entries[4], entries[3], entries[2],
               entries[5]]
    assert plan_shape(swapped) == shape
    assert plan_shape([]) == "empty"
    # ... but the slow-log note still surfaces volatile refusals
    ctx = RequestContext()
    ctx.plan = entries
    note = plan_note(ctx)
    assert note["shape"] == shape
    assert note["refusals"] == ["breaker_open", "no_replica"]


@obs
def test_plan_store_sampling_and_cardinality_bounds():
    store = PlanStore(sample_n=4, max_shapes=2, window_s=0)
    a = [{"stage": "cache", "decision": "hit"}]
    for i in range(9):
        store.observe("qa", a, units=2.0, trace_id=f"t{i}")
    c = store.counters()
    assert c["observations"] == 9
    # systematic 1-in-N: first observation, then counts 4 and 8
    assert c["sampled"] == 3
    snap = store.snapshot()
    agg = snap["shapes"]["qa"]["plans"]["cache=hit"]
    assert agg["count"] == 9
    assert agg["meanUnits"] == 2.0
    assert agg["exemplarTraceIds"] == ["t0", "t3", "t7"]
    assert agg["sampledStages"] == a
    # query-shape bound: third distinct shape folds into 'other'
    store.observe("qb", a)
    store.observe("qc", a)
    snap = store.snapshot()
    assert set(snap["shapes"]) == {"qa", "qb", "other"}
    # per-query-shape plan-shape bound: 'other' overflow bucket
    deep = PlanStore(window_s=0)
    for i in range(MAX_PLAN_SHAPES + 4):
        deep.observe("qs", [{"stage": "tier", "decision": f"d{i}"}])
    plans = deep.snapshot()["shapes"]["qs"]["plans"]
    assert len(plans) == MAX_PLAN_SHAPES + 1
    assert "other" in plans
    # exemplar ring stays bounded
    ring = PlanStore(sample_n=1, window_s=0)
    for i in range(EXEMPLAR_KEEP + 3):
        ring.observe("qs", a, trace_id=f"e{i}")
    ex = ring.snapshot()["shapes"]["qs"]["plans"]["cache=hit"][
        "exemplarTraceIds"
    ]
    assert len(ex) == EXEMPLAR_KEEP
    assert ex[-1] == f"e{EXEMPLAR_KEEP + 2}"


@obs
def test_plan_store_drift_fires_once_and_noop_stays_silent():
    store = PlanStore(window_s=0)
    mesh = [{"stage": "tier", "decision": "mesh"}]
    host = [{"stage": "tier", "decision": "local"}]
    store.observe("qs.drift", mesh)
    assert store.roll_window() == []  # first window: nothing to compare
    store.observe("qs.drift", mesh)
    assert store.roll_window() == []  # no-op republish: same dominant
    assert store.drifted_shapes() == []
    store.observe("qs.drift", host)
    store.observe("qs.drift", host)
    store.observe("qs.drift", mesh)  # minority: dominant is host
    drifts = store.roll_window()
    assert len(drifts) == 1
    assert drifts[0]["shape"] == "qs.drift"
    assert drifts[0]["from"] == "tier=mesh"
    assert drifts[0]["to"] == "tier=local"
    assert store.drifted_shapes() == ["qs.drift"]
    assert store.counters()["drifts"] == {"qs.drift": 1}
    # the sentinel published one plan.drift journal event
    evs = [
        e
        for e in journal.events(kind="plan.drift")
        if e.get("data", {}).get("shape") == "qs.drift"
    ]
    assert evs and evs[-1]["data"]["prev"] == "tier=mesh"
    assert evs[-1]["data"]["now"] == "tier=local"
    # an empty window between observations does not forget the dominant
    assert store.roll_window() == []


# -- end-to-end through the API ------------------------------------------------


def _records(seed, n):
    return random_records(
        random.Random(seed), chrom="1", n=n, n_samples=2
    )


def _app(recs, *, auth=None, **obs_over):
    from sbeacon_tpu.api import BeaconApp

    obs_over.setdefault("slow_query_ms", -1.0)
    cfg = BeaconConfig(
        engine=EngineConfig(microbatch=False),
        observability=ObservabilityConfig(**obs_over),
        auth=auth or AuthConfig(),
    )
    app = BeaconApp(cfg)
    app.engine.add_index(
        build_index(
            recs,
            dataset_id="pl",
            vcf_location="pl.vcf.gz",
            sample_names=["S0", "S1"],
        )
    )
    app.store.upsert(
        "datasets",
        [
            {
                "id": "pl",
                "name": "pl",
                "_assemblyId": "GRCh38",
                "_vcfLocations": ["synthetic://pl"],
            }
        ],
    )
    return app


def _q(rec, granularity="boolean"):
    return {
        "query": {
            "requestedGranularity": granularity,
            "requestParameters": {
                "assemblyId": "GRCh38",
                "referenceName": "1",
                "start": [max(0, rec.pos - 1)],
                "end": [rec.pos + 5],
                "alternateBases": "N",
            },
        }
    }


@obs
def test_ops_plans_aggregates_tracked_requests_only():
    recs = _records(71, 300)
    app = _app(recs)
    try:
        for k in range(3):
            s, _ = app.handle("POST", "/g_variants", body=_q(recs[k]))
            assert s == 200
        s, doc = app.handle("GET", "/ops/plans")
        assert s == 200
        assert set(doc) == PLANS_KEYS
        assert doc["observations"] >= 3
        assert doc["sampled"] >= 1
        assert "g_variants:boolean" in doc["shapes"]
        by_plan = doc["shapes"]["g_variants:boolean"]["plans"]
        # every aggregated fingerprint is built from registered stages
        for pshape, agg in by_plan.items():
            for part in pshape.split(">"):
                assert part.split("=")[0] in PLAN_STAGES
            assert agg["count"] >= 1 and agg["meanUnits"] >= 0.0
        # the sampled stage document records the admission lane
        sampled = [
            a["sampledStages"]
            for a in by_plan.values()
            if a["sampledStages"]
        ]
        assert sampled
        assert any(
            e["stage"] == "admission" for e in sampled[0]
        )
        # probe surfaces never fold: /ops/plans traffic observes nothing
        before = doc["observations"]
        app.handle("GET", "/ops/plans")
        app.handle("GET", "/metrics")
        _, doc2 = app.handle("GET", "/ops/plans")
        assert doc2["observations"] == before
        # ... and lands in neither SLO budgets nor the cost table
        from sbeacon_tpu.slo import PROBE_ROUTE_LABELS

        assert "ops.plans" in PROBE_ROUTE_LABELS
        _, slo_doc = app.handle("GET", "/slo")
        assert "ops.plans" not in slo_doc["routes"]
        _, costs = app.handle("GET", "/ops/costs")
        assert not any("ops.plans" in k for k in costs["shapes"])
        # /metrics carries the plan.* series
        _, metrics = app.handle("GET", "/metrics")
        assert metrics["plan"]["sampled"] >= 1
        assert metrics["plan"]["shapes"] >= 1
    finally:
        app.close()


@obs
def test_explain_gate_404_401_403_and_identical_answers():
    recs = _records(72, 300)
    q = _q(recs[0])
    # disabled: a 404 indistinguishable from the feature not existing
    app = _app(recs)
    try:
        s, doc = app.handle(
            "POST", "/g_variants", query_params={"explain": "1"}, body=q
        )
        assert s == 404
        assert "explain disabled" in json.dumps(doc)
    finally:
        app.close()
    # enabled + worker token: the /fleet/migrate trust boundary
    app = _app(
        recs,
        auth=AuthConfig(worker_token="sek"),
        explain_enabled=True,
    )
    try:
        s, _ = app.handle(
            "POST", "/g_variants", query_params={"explain": "1"}, body=q
        )
        assert s == 401  # no credential
        s, _ = app.handle(
            "POST",
            "/g_variants",
            query_params={"explain": "1"},
            body=q,
            headers={"Authorization": "Bearer wrong"},
        )
        assert s == 403  # wrong credential
        good = {"Authorization": "Bearer sek"}
        s, plain = app.handle("POST", "/g_variants", body=q)
        assert s == 200
        assert "executionPlan" not in plain["meta"]
        s, explained = app.handle(
            "POST",
            "/g_variants",
            query_params={"explain": "1"},
            body=q,
            headers=good,
        )
        assert s == 200
        ep = explained["meta"]["executionPlan"]
        # the plan, and (ISSUE 37) the request's own stage vector
        assert set(ep) - {"besideMs"} == EXECUTION_PLAN_KEYS | {"stagesMs"}
        assert ep["truncated"] is False
        assert ep["shape"] == plan_shape(ep["stages"])
        stages = {e["stage"] for e in ep["stages"]}
        assert stages <= PLAN_STAGES
        assert "admission" in stages and "cache" in stages
        # explain bypasses the response cache: the cache stage says so
        cache = [e for e in ep["stages"] if e["stage"] == "cache"]
        assert cache[0]["decision"] == "off"
        # the ANSWER is identical with and without explain — the plan
        # rides meta only
        strip = lambda d: {k: v for k, v in d.items() if k != "meta"}
        assert strip(explained) == strip(plain)
        # repeated explain stays live (never served from the cache),
        # while the plain repeat hits it
        s, again = app.handle(
            "POST",
            "/g_variants",
            query_params={"explain": "1"},
            body=q,
            headers=good,
        )
        cache = [
            e
            for e in again["meta"]["executionPlan"]["stages"]
            if e["stage"] == "cache"
        ]
        assert cache[0]["decision"] == "off"
    finally:
        app.close()


@obs
def test_slow_query_records_carry_plan_notes():
    recs = _records(73, 200)
    app = _app(recs, slow_query_ms=0.0)  # 0 records everything
    try:
        s, _ = app.handle("POST", "/g_variants", body=_q(recs[0]))
        assert s == 200
        rec = [
            r
            for r in app.slow_log.recent()
            if r["route"] == "g_variants"
        ][-1]
        note = rec["notes"]["plan"]
        assert note["shape"].startswith("admission=")
        for part in note["shape"].split(">"):
            assert part.split("=")[0] in PLAN_STAGES
    finally:
        app.close()


@obs
def test_canary_rounds_fold_probe_plans_and_roll_windows():
    recs = _records(74, 200)
    app = _app(recs)
    try:
        assert app.canary.sync_probes() == 2
        out = app.canary.run_once()
        assert out["probes"] > 0 and out["failures"] == 0
        snap = app.plans.snapshot()
        # the round rolled the drift window even on an idle fleet
        assert snap["windowsRolled"] >= 1
        canary_shapes = [
            k for k in snap["shapes"] if k.startswith("canary:")
        ]
        assert canary_shapes
        # probe plans fold under bounded synthetic shapes, never under
        # tenant query shapes
        assert all(
            k.startswith("canary:") for k in snap["shapes"]
        )
    finally:
        app.close()


# -- the seeded plan regression (acceptance scenario) --------------------------


def _sel_payload():
    return VariantQueryPayload(
        dataset_ids=[],
        reference_name="1",
        start_min=1,
        start_max=1 << 30,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        include_datasets="HIT",
        requested_granularity="record",
        include_samples=True,
        sample_names={f"d{d}": ["S0", "S2"] for d in range(3)},
        selected_samples_only=True,
        no_response_cache=True,
    )


def _assert_same_responses(ra, rb):
    assert len(ra) == len(rb)
    for a, b in zip(ra, rb):
        assert (a.dataset_id, a.vcf_location) == (
            b.dataset_id,
            b.vcf_location,
        )
        assert a.exists == b.exists
        assert a.call_count == b.call_count
        assert a.variants == b.variants
        assert a.sample_indices == b.sample_indices


@obs
@multi_device
def test_plane_budget_flip_drifts_within_one_window(tmp_path):
    """The acceptance scenario end to end: shrinking the plane HBM
    budget flips selected-samples serving from the planes on their
    owner chips to the host's copy. Within ONE window the sentinel publishes
    ``plan.drift``, ``/debug/status`` names the query shape,
    ``/ops/plans`` shows the new dominant with an exemplar resolving
    through ``/_trace`` — and the answers stay byte-identical."""
    from sbeacon_tpu.api import BeaconApp

    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False))
    )
    samples = ["S0", "S1", "S2"]
    shards = []
    for d in range(3):
        rng = random.Random(500 + d)
        shards.append(
            build_index(
                random_records(rng, chrom="1", n=200, n_samples=3),
                dataset_id=f"d{d}",
                vcf_location=f"d{d}.vcf.gz",
                sample_names=samples,
            )
        )
        eng.add_index(shards[-1])
    cfg = BeaconConfig(
        engine=EngineConfig(microbatch=False),
        observability=ObservabilityConfig(slow_query_ms=-1.0),
    )
    app = BeaconApp(cfg, engine=eng)
    qshape = "g_variants:record"
    pay = _sel_payload()

    def run_window(n=2):
        outs = []
        for _ in range(n):
            ctx = RequestContext(route="g_variants")
            with request_context(ctx):
                outs.append(eng.search(pay))
            app.plans.observe(
                qshape, ctx.plan, units=1.0, trace_id=ctx.trace_id
            )
        return outs

    try:
        with tracer.enabled():
            before = run_window()
            assert app.plans.roll_window() == []
            # no-op republish: the planes land on their owners again
            # under the SAME budget — the dominant shape must not move
            for shard in shards:
                eng.add_index(shard)
            run_window()
            assert app.plans.roll_window() == []
            assert app.plans.drifted_shapes() == []
            # the seeded regression: a budget no plane set fits, so
            # the next publish leaves every dataset's planes on the host
            eng.config = dataclasses.replace(
                eng.config,
                engine=dataclasses.replace(
                    eng.config.engine, plane_hbm_budget_gb=1e-9
                ),
            )
            for shard in shards:
                eng.add_index(shard)
            after = run_window()
            drifts = app.plans.roll_window()
            assert len(drifts) == 1
            d = drifts[0]
            assert d["shape"] == qshape and d["from"] != d["to"]
            # the new dominant names the alternative not taken and why
            assert "host_planes" in d["to"]
            assert "planes_budget" in d["to"]
            # byte-identical answers across the flip
            _assert_same_responses(before[0], after[0])
            # journal event
            evs = [
                e
                for e in journal.events(kind="plan.drift")
                if e.get("data", {}).get("shape") == qshape
            ]
            assert evs and "planes_budget" in evs[-1]["data"]["now"]
            # /debug/status diagnosis names the drifted shape
            s, status = app.handle("GET", "/debug/status")
            assert s == 200
            assert qshape in status["diagnosis"]["planDrift"]
            assert status["plans"]["drifts"] == {qshape: 1}
            # /metrics ticks plan.drift{shape}
            _, metrics = app.handle("GET", "/metrics")
            assert metrics["plan"]["drift"] == {qshape: 1}
            # /ops/plans: the aggregate shows the flip with a sampled
            # exemplar, and the stage says how many datasets it read
            # from the host
            s, plans = app.handle("GET", "/ops/plans")
            assert s == 200
            agg = plans["shapes"][qshape]
            assert agg["dominant"] == d["to"]
            assert agg["previousDominant"] == d["from"]
            new = agg["plans"][d["to"]]
            declined = [
                e
                for e in new["sampledStages"]
                if e.get("decision") == "host_planes"
            ]
            assert declined
            assert declined[0]["detail"]["datasets"] == 3
            # ... and the exemplar resolves through /_trace
            exemplar = new["exemplarTraceIds"][0]
            s, tr = app.handle(
                "GET", "/_trace", query_params={"trace_id": exemplar}
            )
            assert s == 200
            assert tr["traces"], "exemplar trace must resolve"
    finally:
        tracer.reset()
        app.close()


# -- the static lint (tier-1 wiring + violation shapes) ------------------------


@obs
def test_plan_stage_lint():
    """Every plan_stage() stage/reason under sbeacon_tpu/ must be a
    literal member of the plan.py registries and every registered
    entry must be used — two-way parity, like the metric catalogue."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_plan_stages.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "registries in sync" in proc.stdout


@obs
def test_plan_stage_lint_catches_violations(tmp_path):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_plan_stages as cps
    finally:
        sys.path.pop(0)
    # registry parsing from a synthetic plan.py
    plan_py = tmp_path / "plan.py"
    plan_py.write_text(
        'PLAN_STAGES = frozenset({"cache", "unused_stage"})\n'
        'PLAN_REASONS = frozenset({"stale"})\n'
    )
    assert cps.registry("PLAN_STAGES", plan_py) == {
        "cache",
        "unused_stage",
    }
    assert cps.registry("MISSING", plan_py) is None
    # scan violations: dynamic stage, extra positional, computed
    # reason, **dynamic expansion
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(
        "plan_stage('cache', decision='hit')\n"
        "plan_stage('bogus')\n"
        "plan_stage(name)\n"
        "plan_stage('cache', 'two')\n"
        "plan_stage('cache', reason=compute())\n"
        "plan_stage('cache', **extra)\n"
    )
    stages, reasons, errors = cps.scan(root)
    assert set(stages) == {"cache", "bogus"}
    assert any("must be a literal" in e for e in errors)
    assert any("exactly one" in e for e in errors)
    assert any("reason= must be a literal" in e for e in errors)
    assert any("**dynamic" in e for e in errors)
    # two-way parity: unregistered use + registered-but-unused, both
    # directions, both registries
    errs = cps.lint(stages, reasons, {"cache", "unused_stage"}, {"stale"})
    assert any("'bogus'" in e for e in errs)
    assert any("'unused_stage'" in e for e in errs)
    assert any("'stale'" in e for e in errs)
    assert cps.lint({}, {}, {"cache"}, set())  # no call sites at all
    assert any(
        "not found" in e for e in cps.lint({"cache": ["x:1"]}, {}, None, set())
    )
