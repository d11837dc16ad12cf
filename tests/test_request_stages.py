"""A request carries its own stages (ISSUE 37).

``RequestContext.stages`` is fed by the stage clock under the rule of a
stage's ``req_ms``: summed over the requests finished between two
snapshots it IS the ``req_ms`` difference, stage by stage; a request's
chain never passes its ``elapsed_ms``; a launch's samples go to every
entry it serves and a pool thread's to ``beside``. ``TailFold`` sums the
vectors of a route's slowest twentieth and middle fifth into the
``request.*`` series that ``benchmark/readers/tail_excess.py``
differences, and the slow-query record and ``?explain=1`` carry the
vector. CPU, the chip's index family forced as tests/test_stages.py does.
"""

import dataclasses
import importlib.util
import json
import threading
from pathlib import Path

import pytest

import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.ops.kernel as kernel_mod
import sbeacon_tpu.telemetry as tel
from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.config import AuthConfig, BeaconConfig
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.serving import MicroBatcher
from sbeacon_tpu.telemetry import RequestContext, request_context
from sbeacon_tpu.testing import synthetic_shard
from sbeacon_tpu.utils import trace as trace_mod
from sbeacon_tpu.utils.trace import (
    CHAIN,
    FOLD_LABELS,
    TailFold,
    Tracer,
    tracer,
)

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
obs = pytest.mark.obs

N_SAMPLES = 40
CLIENTS = 8


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two datasets with genotype planes on the one-chip path (fused
    stack through the batcher for booleans and counts, one pool task a
    dataset for plane-reading records), every request recorded, explain
    open to the worker token: (app, body, finished) where ``finished``
    lists (status, elapsed_ms, ctx) of every request since its last
    ``clear()``."""
    patch = pytest.MonkeyPatch()
    patch.setattr(
        engine_mod, "make_device_index",
        lambda shard, **_kw: ScatterDeviceIndex(shard),
    )
    patch.setattr(tel, "flight_recorder", tel.DeviceFlightRecorder())
    root = tmp_path_factory.mktemp("request_stages_root")
    config = BeaconConfig.from_env(root)
    config = dataclasses.replace(
        config,
        engine=dataclasses.replace(config.engine, use_mesh=False),
        observability=dataclasses.replace(
            config.observability, slow_query_ms=0.0, explain_enabled=True
        ),
        auth=AuthConfig(worker_token="sek"),
    )
    app = BeaconApp(config)
    shards = [
        synthetic_shard(
            4000, n_samples=N_SAMPLES, seed=31 + d, dataset_id=f"rs{d}",
            chroms=["1"], with_gt_planes=True, plane_density=0.2,
        )
        for d in range(2)
    ]
    for d, shard in enumerate(shards):
        app.engine.add_index(shard)
        app.store.upsert("datasets", [{
            "id": f"rs{d}", "name": f"rs{d}", "_assemblyId": "GRCh38",
            "_vcfLocations": [shard.meta.get("vcf_location", f"rs{d}.vcf.gz")],
        }])
    app.engine.warmup()
    pos = int(shards[0].cols["pos"][2000])

    def body(granularity, width):
        return {"query": {
            "requestedGranularity": granularity,
            "includeResultsetResponses": "HIT",
            "requestParameters": {
                "assemblyId": "GRCh38", "referenceName": "1",
                "start": [max(0, pos - width)], "end": [pos + width],
                "alternateBases": "N",
            },
            "pagination": {"skip": 0, "limit": 10},
        }}

    finished = []
    finish = app._finish

    def recording(ctx, route, status, payload, elapsed_ms, tracked):
        finished.append((status, elapsed_ms, ctx))
        return finish(ctx, route, status, payload, elapsed_ms, tracked)

    patch.setattr(app, "_finish", recording)
    try:
        yield app, body, finished
    finally:
        app.close()
        app.engine.close()
        patch.undo()


def _req_ms() -> dict:
    return {n: tracer.stage_counts(n)[2] for n in CHAIN}


def _drive(app, body, finished, attempt: int):
    """Eight concurrent clients, six distinct requests each: counts (the
    fused stack, batched) and records (the pool, a task a dataset).
    (req_ms difference by chain stage, the finished requests)."""
    finished.clear()
    failures = []

    def client(c):
        for k in range(6):
            granularity = "record" if k % 2 else "count"
            width = 3000 + 997 * attempt + 53 * c + 7 * k
            st, doc = app.handle(
                "POST", "/g_variants", body=body(granularity, width)
            )
            if st != 200:
                failures.append((st, doc))

    before = _req_ms()
    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(CLIENTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    after = _req_ms()
    assert not failures, failures[:2]
    assert len(finished) == CLIENTS * 6
    return {n: after[n] - before[n] for n in CHAIN}, list(finished)


@obs
def test_the_vectors_of_the_finished_requests_are_the_stages_req_ms(
    served, monkeypatch
):
    """Tentpole 1's identity, for every chain stage, batched and
    fan-out paths both, and per request the chain within its elapsed
    time. The fetch is held 10 ms so that launches serve several."""
    app, body, finished = served
    fetch = kernel_mod.PendingQueryResults.fetch

    def held(self):
        threading.Event().wait(0.01)
        return fetch(self)

    monkeypatch.setattr(kernel_mod.PendingQueryResults, "fetch", held)
    launches = app.engine._batcher.occupancy
    skipped = app.engine.materialized["skipped"]
    # the stages are process-wide, and apps that earlier tests left open
    # probe their engines now and then: the best of a few readings
    worst = {}
    for attempt in range(3):
        hist_before = dict(launches()["histogram"])
        added, requests = _drive(app, body, finished, attempt)
        hist = {
            k: v - hist_before.get(k, 0)
            for k, v in launches()["histogram"].items()
        }
        worst = {}
        for name in CHAIN:
            own = sum(ctx.stages.get(name, 0.0) for _s, _ms, ctx in requests)
            if abs(own - added[name]) > 1e-3 * abs(added[name]) + 1e-6:
                worst[name] = (own, added[name])
        if not worst:
            break
    assert not worst, worst
    # ... with the ONE engine.materialize scope around the datasets a
    # fused launch matched nothing in (a count's window misses rs1)
    assert app.engine.materialized["skipped"] > skipped
    # both paths ran, and launches served several requests at once
    seen = {n for _s, _ms, ctx in requests for n in ctx.stages}
    assert {"batcher.wait", "kernel.dispatch", "engine.fanout",
            "runner.wait", "filters.resolve", "api.envelope"} <= seen
    assert any(int(k) > 1 and v > 0 for k, v in hist.items()), hist
    # what the pool did for a record request is beside its chain
    assert any(ctx.beside.get("engine.materialize") for _s, _ms, ctx in requests)
    assert all("engine.pool_wait" not in ctx.stages for _s, _ms, ctx in requests)
    # no chain passes its request's elapsed time, but for the ONE
    # overlap found and named (PERF.md 7, PR 37): ``runner.lookup`` is
    # still open while the pool already runs the job it submitted
    # (``runner.wait`` starts inside it, and ends, with whatever the
    # worker does next, before the request's thread is scheduled again)
    for _status, elapsed_ms, ctx in requests:
        chain = sum(ctx.stages.get(n, 0.0) for n in CHAIN)
        after_submit = ctx.stages.get("runner.lookup", 0.0)
        assert chain - after_submit <= elapsed_ms + 0.05, (
            elapsed_ms, dict(ctx.stages),
        )


@obs
def test_a_launch_of_three_feeds_its_three_entries_and_nobody_else():
    shard = synthetic_shard(3000, seed=6, dataset_id="b3", chroms=["1"])
    dindex = ScatterDeviceIndex(shard)
    pos = shard.cols["pos"]
    kernel = ("kernel.encode", "kernel.dispatch", "kernel.readback",
              "kernel.unpack")

    def three_at_once():
        mb = MicroBatcher(max_batch=8, max_wait_ms=300)
        ctxs = [RequestContext(route="g_variants") for _ in range(3)]
        before = {n: tracer.stage_counts(n) for n in kernel}

        def one(i):
            p = int(pos[400 + 600 * i])
            with request_context(ctxs[i]):
                mb.submit(
                    dindex, QuerySpec("1", p, p, 1, 1 << 30),
                    window_cap=512, record_cap=64,
                )

        threads = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        mb.close()
        after = {n: tracer.stage_counts(n) for n in kernel}
        added = {n: tuple(a - b for a, b in zip(after[n], before[n]))
                 for n in kernel}
        return mb.occupancy()["histogram"], ctxs, added

    bystander = RequestContext(route="g_variants")
    with request_context(bystander):
        # process-wide stages: a probe of an app some earlier test left
        # open can land in the reading; a clean one is one launch of three
        for _ in range(3):
            hist, ctxs, added = three_at_once()
            if hist == {3: 1} and all(added[n][0] == 1 for n in kernel):
                break
    assert hist == {3: 1}, "the three did not batch"
    for name in kernel:
        _count, sum_ms, req_ms = added[name]
        assert req_ms == pytest.approx(3 * sum_ms), name
        for ctx in ctxs:
            assert ctx.stages[name] == pytest.approx(sum_ms), name
    for ctx in ctxs:
        assert ctx.stages["batcher.wait"] > 0 and not ctx.beside
    assert not bystander.stages and not bystander.beside


@obs
def test_a_scope_that_serves_none_feeds_beside_only():
    own = Tracer(enabled=False)
    ctx = RequestContext(route="g_variants")
    with request_context(ctx):
        with own.serving(0), own.stage("engine.materialize") as scope:
            pass
        own.observe("engine.pool_wait", 2.5, 0, (ctx,))
        assert dict(ctx.stages) == {}
        assert dict(ctx.beside) == {
            "engine.materialize": scope.ms, "engine.pool_wait": 2.5,
        }
        # a bare count names nobody (the writer's transaction)
        with own.serving(3), own.stage("runner.persist"):
            pass
        assert dict(ctx.stages) == {} and "runner.persist" not in ctx.beside
        # outside the scopes the thread serves its own request again
        with own.stage("engine.plan") as scope:
            pass
        own.observe("runner.wait", 1.5, ctxs=(ctx, None))
    assert dict(ctx.stages) == {"engine.plan": scope.ms, "runner.wait": 1.5}
    assert own.stage_counts("engine.materialize")[2] == 0.0
    assert own.stage_counts("runner.wait") == (1, 1.5, 1.5)


def _vector(**stages) -> dict:
    return {name.replace("_", "."): ms for name, ms in stages.items()}


@obs
def test_the_fold_classes_against_its_thresholds_and_its_labels_add_up():
    fold = TailFold()
    # nothing is classed before a route has thresholds
    fold.fold("g_variants", 500.0, _vector(filters_resolve=400.0), "count")
    assert fold.series()["classed_total"] == 1
    assert fold.series()["tail_count"] == fold.series()["body_count"] == 0
    assert fold.status()["g_variants"]["thresholdsMs"] is None
    # ... which refresh takes over the route's latest finishes
    for ms in range(1, 101):
        fold.fold("g_variants", float(ms), {}, "boolean")
    fold.refresh()
    cuts = fold.status()["g_variants"]["thresholdsMs"]
    assert cuts == {"p40": 41.0, "p60": 61.0, "p95": 96.0}
    before = fold.series()
    classed = [
        # (elapsed_ms, vector, granularity, side)
        (96.0, _vector(filters_resolve=60.0, runner_wait=30.5), "record", "tail"),
        (300.0, _vector(kernel_dispatch=120.0, http_write=9.0), "count", "tail"),
        (41.0, _vector(filters_resolve=20.0, api_envelope=1.0), "boolean", "body"),
        (61.0, _vector(engine_fanout=50.0), "record", "body"),
        (95.9, _vector(filters_resolve=90.0), "record", None),
        (40.9, _vector(filters_resolve=30.0), "count", None),
        (61.1, _vector(filters_resolve=30.0), "default", None),
    ]
    for elapsed_ms, vector, granularity, _side in classed:
        fold.fold("g_variants", elapsed_ms, vector, granularity)
    after = fold.series()
    assert after["classed_total"] - before["classed_total"] == 7
    assert after["tail_count"] - before["tail_count"] == 2
    assert after["body_count"] - before["body_count"] == 2
    assert set(after["tail_ms"]) == set(after["body_ms"]) == set(FOLD_LABELS)
    for side in ("tail", "body"):
        added = {
            name: after[f"{side}_ms"][name] - before[f"{side}_ms"][name]
            for name in FOLD_LABELS
        }
        want = sum(ms for ms, _v, _g, s in classed if s == side)
        assert sum(added.values()) == pytest.approx(want), side
        if side == "tail":
            assert added["filters.resolve"] == 60.0
            assert added["kernel.dispatch"] == 120.0
            # http.write is no chain stage: its 9 ms are between stages
            assert added["unnamed"] == pytest.approx(5.5 + 180.0)
    assert after["tail_by_granularity"] == {"record": 1, "count": 1}
    by = after["classed_by_granularity"]
    assert (by["record"], by["count"], by["boolean"], by["default"]) == (3, 3, 101, 1)
    doc = fold.status()["g_variants"]
    assert (doc["classed"], doc["tail"], doc["body"]) == (108, 2, 2)
    assert doc["tailMeanMs"]["kernel.dispatch"] == 60.0
    assert doc["bodyMeanMs"] == {
        "filters.resolve": 10.0, "engine.fanout": 25.0, "api.envelope": 0.5,
        "unnamed": 15.5,
    }
    # another route has cuts of its own
    fold.fold("individuals", 1000.0, {}, "default")
    assert fold.series()["tail_count"] == after["tail_count"]


@obs
def test_only_a_served_request_is_classed(served):
    """A shed or timed-out request adds to no class and no count; the
    series are what ``/metrics`` renders; the probe's thread refreshes
    the thresholds once a second."""
    app, body, finished = served
    for k in range(trace_mod.CLASS_AFTER + 4):
        st, _doc = app.handle("POST", "/g_variants", body=body("count", 900 + k))
        assert st == 200
    for _ in range(150):
        if app.tails.status()["g_variants"]["thresholdsMs"]:
            break
        threading.Event().wait(0.02)
    doc = app.handle("GET", "/debug/status")[1]["requests"]["g_variants"]
    assert set(doc["thresholdsMs"]) == {"p40", "p60", "p95"}
    assert doc["thresholdsMs"]["p40"] <= doc["thresholdsMs"]["p95"]
    # every later request is at or over the p95 (and the probe's thread,
    # which takes the cuts anew once a route has classed a request since,
    # finds nothing classed since: a refresh that landed inside the loop
    # above would otherwise put its own cuts back a second later)
    fold = app.tails._routes["g_variants"]
    fold.cut_at = fold.total
    fold.cuts = (0.0, 0.0, 0.0)
    before = app.handle("GET", "/metrics")[1]["request"]
    finished.clear()
    st, _doc = app.handle(
        "POST", "/g_variants", body=body("record", 777),
        headers={"X-Beacon-Deadline": "0.000001"},
    )
    assert st == 504
    st, _doc = app.handle("POST", "/g_variants", body=body("record", 778))
    assert st == 200
    after = app.handle("GET", "/metrics")[1]["request"]
    assert after["classed_total"] - before["classed_total"] == 1
    assert after["tail_count"] - before["tail_count"] == 1
    assert after["body_count"] == before["body_count"]
    added = {
        name: after["tail_ms"][name] - before["tail_ms"].get(name, 0.0)
        for name in FOLD_LABELS
    }
    (ok,) = [
        f for f in finished if f[0] == 200 and f[2].route == "g_variants"
    ]
    assert sum(added.values()) == pytest.approx(ok[1])
    assert added["filters.resolve"] == pytest.approx(ok[2].stages["filters.resolve"])
    assert (
        after["tail_by_granularity"]["record"]
        - before["tail_by_granularity"].get("record", 0)
    ) == 1
    text = app.handle("GET", "/metrics", query_params={"format": "prometheus"})[1]
    text = text if isinstance(text, str) else json.dumps(text)
    assert 'sbeacon_request_tail_ms{stage="filters.resolve"}' in text


def _snap(tail_count, body_count, tail_ms, body_ms, **more) -> dict:
    return {"metrics": {"request": {
        "tail_count": tail_count, "body_count": body_count,
        "tail_ms": tail_ms, "body_ms": body_ms, **more,
    }}}


@obs
@pytest.mark.parametrize("metric,want", [
    ("tail_excess_ms", 55.0),
    ("tail_wait_excess_ms", 30.0),
    ("tail_resolve_excess_ms", 20.0),
    ("tail_launch_excess_ms", 0.0),
    ("tail_host_excess_ms", -1.0),
    ("tail_unnamed_excess_ms", 6.0),
    ("tail_requests_share", 5.0),
    ("tail_record_share", 75.0),
])
def test_tail_excess_differences_two_snapshots(metric, want):
    """The eight layer files through their readers, on a window of 80
    requests: four in the tail (mean 150 ms), sixteen in the body (95)."""
    layer = json.loads((BENCH / "layers" / f"{metric}.json").read_text())
    assert layer["layer"] == "serving path, all stages"
    assert layer["moves"] == "query_p95_ms"
    reader = _load(BENCH / "readers" / f"{layer['reader']}.py")
    before = _snap(
        10, 40,
        {"filters.resolve": 1000.0, "runner.wait": 50.0, "unnamed": 10.0},
        {"filters.resolve": 2000.0, "runner.wait": 80.0, "unnamed": 30.0},
        classed_total=200, tail_by_granularity={"record": 2, "count": 8},
    )
    after = _snap(
        14, 56,
        {"filters.resolve": 1400.0, "runner.wait": 150.0, "engine.fanout": 40.0,
         "api.parse": 4.0, "unnamed": 66.0},
        {"filters.resolve": 3280.0, "runner.wait": 160.0, "engine.fanout": 0.0,
         "api.parse": 32.0, "unnamed": 158.0},
        classed_total=280, tail_by_granularity={"record": 5, "count": 9},
    )
    ctx = {"before": before, "after": after, "records": [None] * 80}
    assert reader.read(layer["args"], ctx) == pytest.approx(want)


@obs
def test_the_five_parts_name_every_label_once_and_a_parent_reads_nothing():
    layers = {
        p.stem: json.loads(p.read_text())
        for p in (BENCH / "layers").glob("tail_*_excess_ms.json")
    }
    assert len(layers) == 5 and "stages" not in layers.get("tail_excess_ms", {})
    named = [s for layer in layers.values() for s in layer["args"]["stages"]]
    assert sorted(named) == sorted(FOLD_LABELS)
    # a program without the series (the parent commit): nothing, no raise
    reader = _load(BENCH / "readers" / "tail_excess.py")
    bare = {"metrics": {"request": {"slow_queries": 0}}}
    ctx = {"before": bare, "after": bare, "records": []}
    assert reader.read({}, ctx) is None
    assert reader.read({"stages": ["unnamed"]}, ctx) is None
    ratio = _load(BENCH / "readers" / "counter_ratio.py")
    for name in ("tail_requests_share", "tail_record_share"):
        args = json.loads((BENCH / "layers" / f"{name}.json").read_text())["args"]
        assert ratio.read(args, ctx) is None
    # a side with no request in the window
    idle = _snap(3, 0, {"unnamed": 5.0}, {})
    assert reader.read({}, {"before": idle, "after": idle, "records": []}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in spec["workloads"]]
    new = {e["name"]: e for e in spec["per_layer"] if e["name"].startswith("tail_")}
    assert len(new) == 8
    for name, entry in new.items():
        assert entry["workloads"] == (
            ["kg1.unique"] if name == "tail_record_share" else cells
        )


@obs
def test_a_slow_record_and_an_explained_response_carry_the_vector(served):
    app, body, finished = served
    finished.clear()
    st, doc = app.handle(
        "POST", "/g_variants", query_params={"explain": "1"},
        body=body("record", 1234), headers={"Authorization": "Bearer sek"},
    )
    assert st == 200, doc
    (_status, elapsed_ms, ctx) = finished[-1]
    plan = doc["meta"]["executionPlan"]
    # EXPLAIN with the timings: the plan's own stages, and the vector
    assert isinstance(plan["stages"], list)
    stages, beside = plan["stagesMs"], plan["besideMs"]
    assert {"api.parse", "filters.resolve", "engine.fanout"} <= set(stages)
    assert set(beside) >= {"kernel.dispatch", "engine.materialize"}
    assert all(v > 0 and v == round(v, 3) for v in stages.values())
    assert sum(v for n, v in stages.items() if n in CHAIN) <= elapsed_ms + 0.05
    record = app.slow_log.recent()[-1]
    assert record["traceId"] == doc["meta"]["traceId"]
    assert record["notes"]["stages"] == stages
    assert record["notes"]["beside"] == beside
    assert "cost" in record["notes"] and "plan" in record["notes"]
    # under the threshold nothing is built for a record nobody writes
    app.slow_log.threshold_ms = 1e9
    try:
        n = app.slow_log.count()
        assert app.handle("POST", "/g_variants", body=body("count", 4321))[0] == 200
        assert app.slow_log.count() == n
    finally:
        app.slow_log.threshold_ms = 0.0
