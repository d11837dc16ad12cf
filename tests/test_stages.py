"""Stages (ISSUE 24): one producer per boundary of the serving path.

Every registered stage is seen after a boolean, a record and a filtered
record request through the HTTP front; the chain's ``req_ms`` adds up to
``api.total``; a batch counts its launch stages once in ``sum_ms`` and
once per request in ``req_ms``; ``work`` stages are annotated for the
profiler and ``wait`` stages are not; the counters at the same
boundaries move; and the benchmark's two readers difference what the
program serves. CPU, the chip's index family forced as
tests/test_chip_bringup.py does.
"""

import dataclasses
import gc
import http.client
import importlib.util
import json
import re
import threading
from pathlib import Path

import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.telemetry as tel
from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.api.server import start_background
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.serving import MicroBatcher
from sbeacon_tpu.testing import synthetic_shard
from sbeacon_tpu.utils import trace as trace_mod
from sbeacon_tpu.utils.trace import CHAIN, STAGES, Tracer, tracer

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
obs = pytest.mark.obs

N_SAMPLES = 40
TERMS = ("MONDO:0005001", "MONDO:0005002")


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _submission(ds: str, samples: list) -> dict:
    idx = range(len(samples))
    return {
        "datasetId": ds, "assemblyId": "GRCh38", "vcfLocations": [],
        "dataset": {"name": ds, "description": "stages"}, "index": True,
        "individuals": [
            {"id": f"{ds}-I{i}", "sex": {"id": "NCIT:C16576", "label": "-"},
             "diseases": [{"diseaseCode": {"id": TERMS[i % 2]}}]}
            for i in idx
        ],
        "biosamples": [
            {"id": f"{ds}-B{i}", "individualId": f"{ds}-I{i}"} for i in idx
        ],
        "runs": [
            {"id": f"{ds}-R{i}", "biosampleId": f"{ds}-B{i}",
             "individualId": f"{ds}-I{i}"} for i in idx
        ],
        "analyses": [
            {"id": f"{ds}-A{i}", "runId": f"{ds}-R{i}",
             "biosampleId": f"{ds}-B{i}", "individualId": f"{ds}-I{i}",
             "vcfSampleId": samples[i]} for i in idx
        ],
    }


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two datasets with genotype planes and sample metadata behind the
    HTTP front, scatter family, warmed: (post, app, a hit's body)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(
        engine_mod, "make_device_index",
        lambda shard, **_kw: ScatterDeviceIndex(shard),
    )
    patch.setattr(tel, "flight_recorder", tel.DeviceFlightRecorder())
    root = tmp_path_factory.mktemp("stages_root")
    config = BeaconConfig.from_env(root)
    # the suite's eight virtual devices would route two datasets to the
    # mesh program; the chip's one-device path is the one under test
    config = dataclasses.replace(
        config, engine=dataclasses.replace(config.engine, use_mesh=False)
    )
    app = BeaconApp(config)
    shards = [
        synthetic_shard(
            4000, n_samples=N_SAMPLES, seed=11 + d, dataset_id=f"st{d}",
            chroms=["1"], with_gt_planes=True, plane_density=0.2,
        )
        for d in range(2)
    ]
    for shard in shards:
        app.engine.add_index(shard)
    app.engine.warmup()
    server, _t = start_background(app)
    conn = http.client.HTTPConnection(
        "127.0.0.1", server.server_address[1], timeout=120
    )

    def post(path, body):
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    for d, shard in enumerate(shards):
        st, doc = post(
            "/submit", _submission(f"st{d}", shard.meta["sample_names"])
        )
        assert st == 200, doc
    pos = int(shards[0].cols["pos"][2000])

    def body(granularity, *, width=2000, filters=None):
        q = {
            "requestedGranularity": granularity,
            "includeResultsetResponses": "HIT",
            "requestParameters": {
                "assemblyId": "GRCh38", "referenceName": "1",
                "start": [max(0, pos - width)], "end": [pos + width],
                "alternateBases": "N",
            },
            "pagination": {"skip": 0, "limit": 10},
        }
        if filters:
            q["filters"] = filters
        return {"query": q}

    try:
        yield post, app, body
    finally:
        conn.close()
        server.shutdown()
        server.server_close()
        app.close()
        app.engine.close()
        patch.undo()


def _counts() -> dict:
    return {n: tracer.stage_counts(n)[0] for n in STAGES}


@obs
def test_every_registered_stage_is_seen(served):
    post, app, body = served
    before = _counts()
    for b in (
        body("boolean"),
        body("record", width=3000),
        body("record", width=4000, filters=[
            {"id": TERMS[0], "scope": "individuals",
             "includeDescendantTerms": False}
        ]),
        # the v2 default: the term's closure is read from the ontology
        body("record", width=4500, filters=[
            {"id": TERMS[0], "scope": "individuals"}
        ]),
    ):
        st, doc = post("/g_variants", b)
        assert st == 200, doc
        assert doc["responseSummary"]["exists"] is True
    gc.collect()
    # runner.persist is the writer thread's transaction, after the
    # waiter was released: one sample for whatever jobs were waiting
    runner = app.query_runner
    for _ in range(500):
        if runner._n_persisted_jobs == 4:
            break
        threading.Event().wait(0.01)
    assert runner._n_persisted_jobs == 4 and runner._queue.qsize() == 0
    commits = tracer.stage_counts("runner.persist")[0] - before["runner.persist"]
    assert 1 <= commits == runner._n_persist_commits <= 4
    # the job table's lock is timed only when somebody waits for it:
    # a restarted server's request, reading a job of the process before,
    # behind the writer's transaction
    table = app.query_jobs
    with table._lock:
        waiter = threading.Thread(target=table.get_responses, args=("x",))
        waiter.start()
        threading.Event().wait(0.05)
    waiter.join(10)
    after = _counts()
    unseen = sorted(n for n in STAGES if after[n] <= before[n])
    assert not unseen, f"stages with no sample: {unseen}"
    # /debug/status serves each under its own name, beside the old keys
    _st, doc = app.handle("GET", "/debug/status")
    for name in STAGES:
        assert set(doc["stages"][name]) >= {
            "count", "sum_ms", "req_ms", "cpu_ms", "p50", "p95", "p99",
        }, name
    for old in ("admission_wait_ms", "queue_wait_ms", "exec_ms", "encode_ms",
                "launch_ms", "fetch_ms", "materialize_ms"):
        assert set(doc["stages"][old]) == {"p50", "p95", "p99"}, old
    assert STAGES.get(doc["diagnosis"]["slowestStage"]) != "total"


@obs
def test_an_unregistered_stage_raises():
    with pytest.raises(ValueError, match="unregistered stage"):
        tracer.stage("kernel.nonsense")
    with pytest.raises(ValueError, match="unregistered stage"):
        trace_mod.stage("kernel.nonsense")
    with pytest.raises(ValueError, match="unregistered stage"):
        tracer.observe("nonsense.wait", 1.0)
    # a work stage needs its live scope for the annotation
    with pytest.raises(ValueError, match="must be a `with stage"):
        tracer.observe("kernel.dispatch", 1.0)
    assert set(CHAIN) <= set(STAGES)
    assert all(STAGES[n] in ("work", "wait") for n in CHAIN)
    assert set(STAGES.values()) == {"work", "wait", "total"}


def _sums(names) -> dict:
    return {n: tracer.stage_counts(n) for n in names}


@obs
def test_the_chain_adds_up_to_the_request(served, monkeypatch):
    """Through the runner and the batcher: the chain's req_ms is at most
    api.total and at least 0.9 of it. The device answers in 20 ms, as the
    chip's programs do; against the CPU's 1 ms the untimed glue between
    the stages (about 0.7 ms a request here) would be a tenth."""
    import jax

    device_get = jax.device_get

    def slow_device_get(x):
        threading.Event().wait(0.02)
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", slow_device_get)
    post, _app, body = served
    names = list(CHAIN) + ["api.total"]
    # the stages are process-wide, and apps that earlier tests left open
    # probe their engines every 30 s: the best of a few readings
    readings = []
    for attempt in range(3):
        # distinct requests (a repeat is answered by the job table)
        before = _sums(names)
        for k in range(12):
            width = 5000 + 400 * attempt + 37 * k
            st, _doc = post("/g_variants", body("count", width=width))
            assert st == 200
        after = _sums(names)
        total = after["api.total"][1] - before["api.total"][1]
        assert after["api.total"][0] - before["api.total"][0] == 12
        assert after["batcher.wait"][0] > before["batcher.wait"][0]
        assert after["runner.wait"][0] > before["runner.wait"][0]
        chain = sum(after[n][2] - before[n][2] for n in CHAIN)
        readings.append(chain / total)
        if 0.9 <= chain / total <= 1.001:
            break
    assert any(0.9 <= r <= 1.001 for r in readings), readings


@obs
def test_a_batch_counts_its_launch_once_in_sum_and_per_request_in_req():
    shard = synthetic_shard(3000, seed=5, dataset_id="b2", chroms=["1"])
    dindex = ScatterDeviceIndex(shard)
    pos = shard.cols["pos"]
    launch = ("kernel.dispatch", "kernel.readback", "batcher.pipeline",
              "batcher.fetch_wait")

    def two_at_once():
        """Per launch stage (count, sum_ms, req_ms) added by one batch of
        two, and the batcher.wait samples."""
        mb = MicroBatcher(max_batch=8, max_wait_ms=300)
        before = _sums(launch + ("batcher.wait",))
        out = [None, None]

        def one(i):
            p = int(pos[500 + 700 * i])
            out[i] = mb.submit(
                dindex, QuerySpec("1", p, p, 1, 1 << 30),
                window_cap=512, record_cap=64,
            )

        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        mb.close()
        assert all(r is not None for r in out)
        assert mb.occupancy()["histogram"] == {2: 1}, "the two did not batch"
        after = _sums(launch + ("batcher.wait",))
        return {n: tuple(a - b for a, b in zip(after[n], before[n]))
                for n in after}

    # process-wide stages: a probe of an app some earlier test left open
    # can land in the reading; a clean one has one sample per launch stage
    for _ in range(3):
        added = two_at_once()
        if all(added[n][0] == 1 for n in launch):
            break
    for name in launch:
        d_count, d_sum, d_req = added[name]
        assert d_count == 1, name
        assert d_req == pytest.approx(2 * d_sum), name
    # a submission's own wait is one sample each, n = 1
    assert added["batcher.wait"][0] == 2


@obs
def test_work_stages_are_annotated_and_wait_stages_are_not(monkeypatch, request):
    import jax.profiler

    seen = []
    me = threading.get_ident()

    class Annotation:
        def __init__(self, name):
            self.name = name

        is_enabled = staticmethod(lambda: True)

        # this thread's alone: an earlier test's unclosed app may pass
        # its own work stages on its own threads meanwhile
        def __enter__(self):
            if threading.get_ident() == me:
                seen.append(("open", self.name))

        def __exit__(self, *exc):
            if threading.get_ident() == me:
                seen.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    # ... and no collection of this thread's own inside the scopes below
    gc.disable()
    request.addfinalizer(gc.enable)
    own = Tracer(enabled=False)
    with own.stage("kernel.dispatch") as st:
        pass
    assert seen == [("open", "beacon.kernel.dispatch"),
                    ("close", "beacon.kernel.dispatch")]
    assert st.ms >= 0 and own.stage_counts("kernel.dispatch")[0] == 1
    del seen[:]
    with own.stage("api.admit"):
        pass
    own.observe("runner.wait", 3.0)
    own.observe("api.total", 5.0)
    assert seen == []
    assert own.stage_counts("runner.wait") == (1, 3.0, 3.0)
    # nobody captures a profile: a work stage opens no annotation either
    monkeypatch.setattr(Annotation, "is_enabled", staticmethod(lambda: False))
    with own.stage("kernel.dispatch"):
        pass
    assert seen == [] and own.stage_counts("kernel.dispatch")[0] == 2
    monkeypatch.setattr(Annotation, "is_enabled", staticmethod(lambda: True))
    # a collection is the gc stage, annotated by its generation
    before = tracer.stage_counts("gc")[0], list(trace_mod.gc_pauses)
    trace_mod._gc_hook("start", {"generation": 2})
    trace_mod._gc_hook("stop", {"generation": 2, "collected": 0})
    assert seen == [("open", "beacon.gc.gen2"), ("close", "beacon.gc.gen2")]
    assert tracer.stage_counts("gc")[0] == before[0] + 1
    assert trace_mod.gc_pauses[2] == before[1][2] + 1
    # with the span tree on, the same scope is also a Span: one reading
    own.enable()
    with own.serving(3), own.stage("engine.plan") as st:
        st.note(targets=2)
    tree = own.recent_trees()[-1]
    assert tree["name"] == "engine.plan" and tree["meta"] == {"targets": 2}
    count, sum_ms, req_ms = own.stage_counts("engine.plan")
    assert count == 1 and req_ms == pytest.approx(3 * sum_ms)
    assert tree["elapsedMs"] == pytest.approx(sum_ms, abs=2e-3)


class _NoLock:
    """Stands where a stage's lock is: a writer that takes it fails."""

    def __enter__(self):
        raise AssertionError("a writer took the stage's lock")

    def __exit__(self, *exc):
        return False


@obs
@pytest.mark.parametrize("n,want_req", [(1, 1.0), (0, 0.0)])
def test_a_sample_takes_no_lock_and_allocates_no_scope(n, want_req):
    """The request path (n = 1) and a fan-out's pool threads (n = 0,
    thirty-two targets a request in ``mds.fanout``) write a sample
    without the stage's lock and without a scope object of their own;
    a reader folds both, and only samples that served a request count
    in ``req_ms`` (PERF.md 6, PR 24: the cell's tail moved with both)."""
    own = Tracer(enabled=False)
    acc = own.stage("engine.materialize")
    real, acc._lock = acc._lock, _NoLock()
    assert own.serving(n) is own.serving(n)
    for _ in range(3):
        with own.serving(n), own.stage("engine.materialize"):
            pass
    own.observe("runner.wait", 2.0, n)
    acc._lock = real
    count, sum_ms, req_ms = own.stage_counts("engine.materialize")
    assert count == 3 and req_ms == pytest.approx(want_req * sum_ms)
    assert own.stage_counts("runner.wait") == (1, 2.0, 2.0 * want_req)
    assert len(own.stage_quantiles("engine.materialize")) == 3
    # outside the scope the thread serves one request again
    with own.stage("engine.plan"):
        pass
    count, sum_ms, req_ms = own.stage_counts("engine.plan")
    assert count == 1 and req_ms == sum_ms


@obs
def test_the_table_s_lock_is_free_when_its_wait_is_recorded(monkeypatch):
    """``runner.table_wait`` is handed to the stage after the release:
    nothing but the table's own work runs under the lock every request
    queues on."""
    from sbeacon_tpu import query_jobs

    lock = query_jobs._TableLock()
    held_at_observe = []
    monkeypatch.setattr(
        query_jobs.tracer, "observe",
        lambda name, ms, n=1: held_at_observe.append(
            (name, lock._lock.locked())
        ),
    )
    with lock:  # uncontended: not timed
        pass
    assert held_at_observe == []
    entered, leave = threading.Event(), threading.Event()

    def holder():
        with lock:
            entered.set()
            leave.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    assert entered.wait(5)
    threading.Timer(0.05, leave.set).start()
    with lock:  # contended: waits for the holder
        assert lock._lock.locked()
    t.join(5)
    assert held_at_observe == [("runner.table_wait", False)]


@obs
def test_the_counters_at_the_same_boundaries(served):
    post, app, body = served

    def metrics():
        return app.handle("GET", "/metrics")[1]

    m0 = metrics()
    b = body("count", width=7777)
    assert post("/g_variants", b)[0] == 200
    m1 = metrics()
    assert post("/g_variants", b)[0] == 200
    m2 = metrics()
    runner = lambda m, k: m["runner"][k]
    assert runner(m1, "submits") - runner(m0, "submits") == 1
    assert runner(m1, "memory_hits") == runner(m0, "memory_hits")
    assert runner(m2, "submits") - runner(m1, "submits") == 1
    assert runner(m2, "memory_hits") - runner(m1, "memory_hits") == 1
    # the repeat reached neither the engine nor the device
    assert m2["device"]["launches"] == m1["device"]["launches"]
    # one fused launch of a two-dataset query evaluates 1 x 2 pairs
    fused = lambda m: m["device"]["launches"].get("fused", 0)
    assert fused(m1) - fused(m0) == 1
    assert (m1["device"]["evaluated_pairs"]
            - m0["device"]["evaluated_pairs"]) == 2
    assert set(m2["runtime"]) == {
        "gc_pauses", "gc_pause_ms", "thread_cpu_ms", "thread_yields",
        "thread_preempted", "process_cpu_ms", "host_cpus", "stage_cpu_every",
    }
    assert set(m2["runtime"]["gc_pauses"]) == {"0", "1", "2"}
    gc.collect()
    m3 = metrics()
    assert m3["runtime"]["gc_pauses"]["2"] > m2["runtime"]["gc_pauses"]["2"]
    assert m3["runtime"]["gc_pause_ms"] > m2["runtime"]["gc_pause_ms"]


# -- the benchmark's readers ----------------------------------------------------


def _snap(**stages) -> dict:
    return {"stages": {
        n: {"count": c, "sum_ms": s, "req_ms": r} for n, (c, s, r) in stages.items()
    }}


@obs
@pytest.mark.parametrize("args,want", [
    ({"stages": ["a.x", "b.y"], "per": "request"}, (30.0 + 8.0) / 4),
    ({"stages": ["a.x"], "per": "sample"}, 30.0 / 10),
    ({"stages": ["a.x"], "per": "sample", "samples_of": ["b.y"]}, 30.0 / 2),
    ({"stages": ["a.x"], "minus": ["b.y"], "per": "request"}, (30.0 - 8.0) / 4),
    ({"stages": ["a.x"], "minus": ["no.such"], "per": "request"}, None),
    ({"stages": ["a.x"], "field": "req_ms", "per": "stage", "over": ["b.y"],
      "scale": 100}, 100 * 60.0 / 8.0),
    # a stage the program does not have, and a divisor of zero
    ({"stages": ["a.x", "no.such"], "per": "request"}, None),
    ({"stages": ["a.x"], "per": "stage", "over": ["no.such"]}, None),
    ({"stages": ["idle.z"], "per": "sample"}, None),
])
def test_stage_delta_differences_two_snapshots(args, want):
    reader = _load(BENCH / "readers" / "stage_delta.py")
    ctx = {
        "before": _snap(**{"a.x": (5, 10.0, 20.0), "b.y": (1, 2.0, 2.0),
                           "idle.z": (7, 1.0, 1.0)}),
        "after": _snap(**{"a.x": (15, 40.0, 80.0), "b.y": (3, 10.0, 10.0),
                          "idle.z": (7, 1.0, 1.0)}),
        "records": [None] * 4,
    }
    got = reader.read(args, ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    # the parent's /debug/status has no such entries at all
    bare = {"before": {"stages": {}}, "after": {"stages": {}}, "records": [1]}
    assert reader.read(args, bare) is None


@obs
def test_gap_names_reads_the_reduced_trace():
    reader = _load(BENCH / "readers" / "gap_names.py")
    reduce_mod = _load(BENCH / "trace_reduce.py")
    fixture = json.loads((BENCH / "testdata" / "stage_events.json").read_text())
    trace = reduce_mod.reduce_events(fixture["events"])
    names = [name for name, _s in trace["idle_gaps"]]
    assert names == fixture["expect"]["gap_names"]
    got = reader.read({"prefix": "beacon."}, {"trace": trace})
    assert got == pytest.approx(fixture["expect"]["named_share"])
    # the old recording has PJRT's names only: nothing is named
    old = json.loads((BENCH / "testdata" / "trace_events.json").read_text())
    assert reader.read(
        {"prefix": "beacon."}, {"trace": reduce_mod.reduce_events(old["events"])}
    ) == 0.0
    assert reader.read({"prefix": "beacon."}, {"trace": None}) is None
    assert reader.read({"prefix": "beacon."}, {"trace": {"idle_gaps": []}}) is None


@obs
def test_the_layer_files_read_the_program_s_own_names():
    """The coverage metric reads exactly the chain; every stage a layer
    file names is registered; host work is every work stage but the two
    that wait for the device or the collector, and the two that lie
    inside another (``filters.descendants`` in ``filters.resolve``,
    ``engine.select`` in ``engine.plan``)."""
    layers = {p.stem: json.loads(p.read_text())
              for p in (BENCH / "layers").glob("*.json")}
    assert layers["span_coverage"]["args"]["stages"] == list(CHAIN)
    assert layers["span_coverage"]["args"]["over"] == ["api.total"]
    work = [n for n, k in STAGES.items() if k == "work"]
    assert sorted(layers["host_work_ms_per_query"]["args"]["stages"]) == sorted(
        set(work)
        - {"kernel.readback", "gc", "filters.descendants", "engine.select"}
    )
    for name, layer in layers.items():
        if layer["reader"] != "stage_delta":
            continue
        a = layer["args"]
        for stage in (a["stages"] + a.get("over", []) + a.get("samples_of", [])
                      + a.get("minus", [])):
            assert stage in STAGES, (name, stage)


@obs
def test_roofline_patterns_match_the_declared_programs():
    """Every MODULES pattern of benchmark/rooflines/<family>.py matches
    the ``jit_<name>`` of a function the program declares for that
    family, and the declared functions exist: a rename fails here
    instead of silently dropping ``<family>_kernel_ms``."""
    import sbeacon_tpu.ops.kernel as kernel
    import sbeacon_tpu.ops.scatter_kernel as scatter
    import sbeacon_tpu.parallel.mesh as mesh

    assert set(tel.DEVICE_PROGRAMS) <= set(tel.DEVICE_FAMILIES)
    for family, names in tel.DEVICE_PROGRAMS.items():
        for fn in names:
            assert any(hasattr(m, fn) for m in (kernel, scatter, mesh)), (family, fn)
    for path in sorted((BENCH / "rooflines").glob("*.py")):
        family = path.stem
        jitted = [f"jit_{fn}" for fn in tel.DEVICE_PROGRAMS[family]]
        for pattern in _load(path).MODULES:
            assert any(re.search(pattern, j) for j in jitted), (family, pattern)


@obs
def test_the_profiler_region_machinery_is_gone(monkeypatch, tmp_path):
    assert not hasattr(tel, "profile_region") and not hasattr(tel, "profiler")
    assert BeaconConfig().observability.profiler_port == 0
    monkeypatch.setenv("BEACON_PROFILER_PORT", "9012")
    config = BeaconConfig.from_env(tmp_path)
    assert config.observability.profiler_port == 9012
    assert not hasattr(EngineConfig(), "timing_window")
    assert np.isfinite(tracer.stage_counts("api.total")[1])
