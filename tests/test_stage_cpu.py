"""The stage clock reads its thread's CPU beside its wall (ISSUE 36).

A scope that computes reads ``cpu_ms`` near its wall and one that sleeps
reads none; ``cpu_ms`` folds as ``sum_ms`` does, without a lock and
without losing a sample, and never passes it; samples that were observed
have no thread and read 0. Beside the stages: the process's Python
threads by role (``runtime.thread_cpu_ms`` and its siblings, monotone
across a thread's exit, never above the whole process), the probe that
measures the interpreter lock's turn, and the ten layer files that read
all of it for the benchmark. CPU only: a time here is the sandbox's.
"""

import gc
import http.client
import importlib.util
import json
import re
import sys
import threading
import time
from pathlib import Path

import pytest

from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.api.server import start_background
from sbeacon_tpu.utils import trace as trace_mod
from sbeacon_tpu.utils.trace import (
    FOLD_AT, ROLES, STAGES, THREAD_ROLES, ThreadClock, Tracer, thread_role,
)

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
obs = pytest.mark.obs

CELLS = ["kg1.unique", "mds.fanout", "kg1.samples", "kg4.samples",
         "kg1.samples-desc", "mdsp.samples", "mds4.fanout", "ukb1.samples"]
CPU_PARTS = ["http_cpu_ms", "filters_resolve_cpu_ms", "runner_cpu_ms",
             "kernel_launch_cpu_ms", "materialize_cpu_ms"]
NEW_LAYERS = ["host_cpu_ms_per_query", *CPU_PARTS, "python_cpu_ms_per_query",
              "process_cpu_ms_per_query", "yields_per_query", "lock_turn_ms"]


def _spin(ms: float) -> None:
    """Burn ``ms`` of THIS thread's CPU, however long the suite's other
    workers and threads make that take on the wall."""
    end = time.thread_time() + ms / 1e3
    while time.thread_time() < end:
        pass


def _cpu(tracer: Tracer, name: str) -> float:
    return tracer.stage_summary()[name]["cpu_ms"]


# -- the scope's second clock ---------------------------------------------------


@obs
@pytest.mark.parametrize("busy", [True, False], ids=["spins", "sleeps"])
def test_a_scope_reads_what_it_cost_the_processor_not_what_it_waited(busy):
    own = Tracer(enabled=False)
    for _ in range(5):  # a preempted spin's wall reads long: the best of a few
        own.reset_stages()
        c0 = time.thread_time_ns()
        with own.stage("kernel.encode") as scope:
            _spin(20) if busy else time.sleep(0.02)
        around = (time.thread_time_ns() - c0) * 1e-6
        _count, wall, _req = own.stage_counts("kernel.encode")
        cpu = _cpu(own, "kernel.encode")
        assert wall == pytest.approx(scope.ms) and wall >= 20.0
        # the scope's two reads lie inside the test's own
        assert cpu <= wall and cpu <= around + 0.001
        if not busy or cpu >= 0.8 * wall:
            break
    if busy:
        # within a fifth of its wall; where the suite's load kept the
        # thread off its processor in all five, of the CPU it did get
        assert 20.0 <= cpu and (0.8 * wall <= cpu or 0.9 * around <= cpu)
    else:
        assert cpu < 1.0


@obs
def test_cpu_folds_as_the_wall_does_from_eight_threads_with_no_sample_lost():
    """More than ``FOLD_AT`` samples from eight threads, so writers fold
    while others write: the stage's ``cpu_ms`` is the sum of what each
    thread read on its own clock around its own scopes, it only grows,
    and no thread took the stage's lock for a sample it did not fold."""
    own = Tracer(enabled=False)
    acc = own.stage("engine.materialize")
    per_thread, seen, n = [0.0] * 8, [], FOLD_AT // 4

    def work(i):
        spent = 0
        for _ in range(n):
            c0 = time.thread_time_ns()
            with acc:
                _spin(0.02)
            spent += time.thread_time_ns() - c0
        per_thread[i] = spent * 1e-6

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter over mid-scope
    try:
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            seen.append(_cpu(own, "engine.materialize"))
            time.sleep(0.002)
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    seen.append(_cpu(own, "engine.materialize"))
    count, wall, req = own.stage_counts("engine.materialize")
    assert count == 8 * n > FOLD_AT and req == pytest.approx(wall)
    assert seen == sorted(seen) and seen[-1] > 0.0
    # each thread's own reading encloses its scopes' (the two reads a
    # scope adds lie between them), so the stage reads a little less
    assert 0.5 * sum(per_thread) < seen[-1] <= sum(per_thread)
    assert seen[-1] <= wall * 1.01


@obs
def test_observed_samples_untouched_stages_totals_and_reset():
    own = Tracer(enabled=False)
    own.observe("batcher.wait", 3.0)
    own.observe("handoff.back", 2.0, 4)
    own.observe("api.total", 2.5)
    with own.stage("engine.fanout"):  # a wait stage that is scoped
        _spin(2)
    doc = own.stage_summary()
    # every stage serves the field, before its first sample too
    assert set(doc) == set(STAGES)
    assert all("cpu_ms" in entry for entry in doc.values())
    assert doc["batcher.wait"]["cpu_ms"] == 0.0 == doc["handoff.back"]["cpu_ms"]
    assert doc["batcher.wait"]["sum_ms"] == 3.0
    assert doc["kernel.dispatch"] == {
        "count": 0, "sum_ms": 0.0, "req_ms": 0.0, "cpu_ms": 0.0,
    }
    assert doc["api.total"]["cpu_ms"] == 0.0 and doc["api.total"]["sum_ms"] == 2.5
    assert 1.0 < doc["engine.fanout"]["cpu_ms"] <= doc["engine.fanout"]["sum_ms"]
    own.reset_stages()
    assert own.stage_summary()["engine.fanout"]["cpu_ms"] == 0.0
    assert own.stage_counts("engine.fanout") == (0, 0.0, 0.0)


@obs
@pytest.mark.parametrize("n", [0, 1, 3])
def test_a_pool_thread_s_scope_adds_its_cpu_as_its_wall(n):
    """``tracer.serving(0)`` (a fan-out's pool thread), the request's
    own thread and a launch that serves three: the scope's CPU counts
    once in ``cpu_ms`` as its wall counts once in ``sum_ms``, whatever
    ``req_ms`` makes of it."""
    own = Tracer(enabled=False)

    def task():
        with own.serving(n), own.stage("engine.materialize"):
            _spin(5)

    t = threading.Thread(target=task, name="engine-scatter_0")
    t.start()
    t.join()
    count, wall, req = own.stage_counts("engine.materialize")
    cpu = _cpu(own, "engine.materialize")
    assert count == 1 and req == pytest.approx(n * wall)
    assert 0.0 < cpu <= wall


@obs
def test_a_collection_reads_its_cpu_too():
    trace_mod.install_gc_stage()
    before = trace_mod.tracer.stage_summary()["gc"]
    gc.collect()
    after = trace_mod.tracer.stage_summary()["gc"]
    assert after["count"] > before["count"]
    assert after["cpu_ms"] > before["cpu_ms"]
    assert after["cpu_ms"] <= after["sum_ms"] * 1.01


@obs
def test_no_stage_reads_more_cpu_than_wall_under_eight_spinning_threads():
    own = Tracer(enabled=False)
    names = [n for n, kind in STAGES.items() if kind != "total"][:8]
    stop = threading.Event()

    def work(name):
        while not stop.is_set():
            with own.stage(name):
                _spin(0.3)

    threads = [threading.Thread(target=work, args=(n,)) for n in names]
    for t in threads:
        t.start()
    readings = []
    for _ in range(12):
        time.sleep(0.005)
        readings.append(own.stage_summary())
    stop.set()
    for t in threads:
        t.join()
    readings.append(own.stage_summary())
    for doc in readings:
        for name in names:
            assert doc[name]["cpu_ms"] <= doc[name]["sum_ms"] * 1.01 + 0.002, name
    assert all(readings[-1][n]["cpu_ms"] > 0.0 for n in names)


@obs
def test_an_empty_scope_costs_at_most_the_two_clock_reads_more(monkeypatch):
    """The scope with its CPU clock against the same scope with the
    clock taken out (``int()`` answers 0 without a system call, and a
    zero is not written): what ISSUE 36 added to every stage. The issue
    allows 1.5 us; the best of a few rounds, since the suite's other
    workers share the processors."""
    own = Tracer(enabled=False)
    acc = own.stage("cache.lookup")

    def cost(rounds=5, n=20000):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n):
                with acc:
                    pass
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        return best

    for attempt in range(4):
        after = cost()
        with monkeypatch.context() as patch:
            patch.setattr(trace_mod, "_cpu_ns", int)
            before = cost()
        print(f"empty scope: {before:.2f} us without the CPU clock, {after:.2f} with")
        if after - before < 1.5:
            break
    assert after - before < 1.5, (before, after)
    assert own.stage_counts("cache.lookup")[0] == (attempt + 1) * 2 * 5 * 20000


@obs
def test_where_the_clock_is_dear_one_scope_in_a_stride_reads_it(monkeypatch):
    """The stride follows the clock's measured cost (1 on plain Linux,
    six per microsecond of its cost: about 36 at the 6 us a system call
    costs under gVisor), and a
    stage with stride k reads the clock in every k-th scope and counts
    that reading k times: right over many scopes at a k-th of the cost."""
    assert trace_mod.cpu_clock_stride() == 1 == Tracer(enabled=False).cpu_every
    real = time.thread_time_ns

    def dear():
        _spin(0.006)
        return real()

    monkeypatch.setattr(trace_mod, "_cpu_ns", dear)
    assert 30 <= trace_mod.cpu_clock_stride() <= 90
    reads = []

    def ticking():  # a clock that moves 1 ms between any two readings
        reads.append(1)
        return len(reads) * 1_000_000

    monkeypatch.setattr(trace_mod, "_cpu_ns", ticking)
    own = Tracer(enabled=False)
    acc = own.stage("kernel.unpack")
    acc._every = acc._due = 4
    reads.clear()  # the tracer measured its clock
    for _ in range(40):
        with acc:
            pass
    assert own.stage_counts("kernel.unpack")[0] == 40
    assert _cpu(own, "kernel.unpack") == pytest.approx(10 * 1.0 * 4)
    assert len(reads) == 2 * 10
    # a scope closed early and closed again reads nothing twice
    with acc as scope:
        scope.close()
    assert own.stage_counts("kernel.unpack")[0] == 41


# -- the threads by role --------------------------------------------------------


@obs
def test_the_role_table_names_threads_the_program_starts():
    """Every prefix of ``THREAD_ROLES`` but the interpreter's own
    ``MainThread`` is a thread name (or pool prefix) somewhere under
    ``sbeacon_tpu/``: a renamed pool fails here instead of falling to
    ``other`` in silence."""
    source = "\n".join(
        p.read_text() for p in (REPO / "sbeacon_tpu").rglob("*.py")
    )
    for prefix in THREAD_ROLES:
        if prefix != "MainThread":
            assert re.search(rf'"{re.escape(prefix)}"', source), prefix
    assert ROLES == (
        "request", "query-runner", "engine-scatter", "kernel-launch",
        "kernel-fetch", "batch-drain", "query-jobs-writer", "canary-prober",
        "main", "other",
    )
    assert thread_role("kernel-launch_3") == "kernel-launch"
    assert thread_role("query-runner_0") == "query-runner"
    assert thread_role("MainThread") == "main"
    assert thread_role("Thread-7 (serve_forever)") == "other"
    assert thread_role("lock-turn-probe") == "other"


@obs
def test_a_role_s_cpu_rises_with_its_thread_and_stays_after_it():
    clock = ThreadClock()
    clock.KEEP_S = 0.0  # every read scans
    go, done, leave = threading.Event(), threading.Event(), threading.Event()

    def work():
        go.wait(5)
        _spin(60)
        done.set()
        leave.wait(5)  # alive until read once more

    first = clock.read()
    assert set(first) == {"cpu_ms", "yields", "preempted", "process_cpu_ms"}
    assert all(set(first[k]) == set(ROLES) for k in ("cpu_ms", "yields", "preempted"))
    t = threading.Thread(target=work, name="kernel-fetch_1")
    t.start()
    go.set()
    assert done.wait(10)
    alive = clock.read()
    rose = alive["cpu_ms"]["kernel-fetch"] - first["cpu_ms"]["kernel-fetch"]
    assert 30.0 < rose < 200.0
    assert alive["yields"]["kernel-fetch"] >= first["yields"]["kernel-fetch"]
    leave.set()
    t.join(5)
    gone = clock.read()
    assert gone["cpu_ms"]["kernel-fetch"] == alive["cpu_ms"]["kernel-fetch"]
    assert gone["yields"]["kernel-fetch"] == alive["yields"]["kernel-fetch"]
    # this thread read them all: it is ``main`` or, under a worker, other
    for doc in (first, alive, gone):
        assert sum(doc["cpu_ms"].values()) <= doc["process_cpu_ms"]
    assert gone["process_cpu_ms"] >= alive["process_cpu_ms"]


@obs
def test_one_scan_serves_every_family_of_a_rendering(monkeypatch):
    clock = ThreadClock()
    scans = []
    real = clock._scan
    monkeypatch.setattr(clock, "_scan", lambda: scans.append(1) or real())
    monkeypatch.setattr(clock, "KEEP_S", 60.0)
    docs = [clock.read() for _ in range(4)]
    assert len(scans) == 1 and all(d is docs[0] for d in docs)
    monkeypatch.setattr(clock, "KEEP_S", 0.0)
    clock.read()
    assert len(scans) == 2


@obs
def test_a_thread_that_ends_by_design_leaves_its_final_reading():
    """A connection's handler and a transient drainer end between two
    scans (the benchmark's clients hang up before its second snapshot):
    ``leave`` keeps what no scan could read any more."""
    clock = ThreadClock()
    clock.KEEP_S = 0.0
    first = clock.read()

    def work():
        _spin(30)
        clock.leave()

    t = threading.Thread(target=work, name="batch-drain")
    t.start()
    t.join(5)
    for doc in (clock.read(), clock.read()):  # counted once, and kept
        rose = doc["cpu_ms"]["batch-drain"] - first["cpu_ms"]["batch-drain"]
        assert 25.0 < rose < 100.0
        assert sum(doc["cpu_ms"].values()) <= doc["process_cpu_ms"]


@pytest.fixture()
def served():
    app = BeaconApp()
    server, _t = start_background(app)

    def connect():
        return http.client.HTTPConnection(
            "127.0.0.1", server.server_address[1], timeout=60
        )

    conn = connect()

    def get(path, conn=conn):
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    try:
        yield app, get, connect
    finally:
        conn.close()
        server.shutdown()
        server.server_close()
        app.close()


@obs
def test_metrics_serve_the_runtime_s_cpu_by_role(served):
    """Over HTTP: the connection's thread is the ``request`` role and
    lives as long as the connection, the families share their roles,
    the Python threads never read above the whole process, and the
    counters only grow."""
    app, get, connect = served
    status, doc = get("/info")
    assert status == 200
    _st, m1 = get("/metrics")
    for _ in range(20):
        get("/info")
    time.sleep(2.5 * ThreadClock.KEEP_S)
    _st, m2 = get("/metrics")
    for m in (m1, m2):
        runtime = m["runtime"]
        for family in ("thread_cpu_ms", "thread_yields", "thread_preempted"):
            assert set(runtime[family]) == set(ROLES), family
        assert sum(runtime["thread_cpu_ms"].values()) <= runtime["process_cpu_ms"]
        assert runtime["host_cpus"] >= 1 and runtime["stage_cpu_every"] == 1
    r1, r2 = m1["runtime"], m2["runtime"]
    assert r2["thread_cpu_ms"]["request"] > r1["thread_cpu_ms"]["request"] > 0.0
    assert r2["thread_yields"]["request"] > r1["thread_yields"]["request"]
    assert r2["process_cpu_ms"] > r1["process_cpu_ms"]
    for family in ("thread_cpu_ms", "thread_yields", "thread_preempted"):
        assert all(r2[family][role] >= r1[family][role] for role in ROLES), family
    # the stages of the same requests, with their CPU, in /debug/status
    _st, status = get("/debug/status")
    read = status["stages"]["http.read"]
    assert 0.0 < read["cpu_ms"] <= read["sum_ms"] * 1.01
    assert status["stages"]["api.total"]["cpu_ms"] == 0.0  # observed
    # a second connection's thread ends with it, and its CPU stays
    other = connect()
    for _ in range(20):
        get("/info", other)
    other.close()
    time.sleep(2.5 * ThreadClock.KEEP_S)
    r3 = get("/metrics")[1]["runtime"]
    assert r3["thread_cpu_ms"]["request"] - r2["thread_cpu_ms"]["request"] > 0.5 * (
        r2["thread_cpu_ms"]["request"] - r1["thread_cpu_ms"]["request"]
    )
    text = app.handle("GET", "/metrics", {"format": "prometheus"})[1]
    assert 'sbeacon_runtime_thread_cpu_ms{role="request"}' in text
    assert "sbeacon_runtime_host_cpus " in text


# -- the lock's turn --------------------------------------------------------------


def _probes() -> list:
    return [t for t in threading.enumerate() if t.name == "lock-turn-probe"]


@obs
def test_the_probe_starts_with_the_app_records_and_ends_with_close():
    assert STAGES["runtime.lock_turn"] == "wait"
    assert "runtime.lock_turn" not in trace_mod.CHAIN
    before = len(_probes())
    count0 = trace_mod.tracer.stage_counts("runtime.lock_turn")[0]
    app = BeaconApp()
    try:
        assert len(_probes()) == before + 1
        assert app.lock_probe._thread.daemon
        for _ in range(100):
            if trace_mod.tracer.stage_counts("runtime.lock_turn")[0] >= count0 + 2:
                break
            time.sleep(0.05)
        count, sum_ms, req_ms = trace_mod.tracer.stage_counts("runtime.lock_turn")
        assert count >= count0 + 2
        doc = app.handle("GET", "/debug/status")[1]["stages"]["runtime.lock_turn"]
        assert doc["count"] >= count and doc["cpu_ms"] == 0.0
        assert doc["sum_ms"] >= 0.0 and "p50" in doc
    finally:
        app.close()
    assert len(_probes()) == before
    assert not app.lock_probe._thread.is_alive()
    app.close()  # twice is harmless


@obs
def test_the_probe_of_an_app_nobody_closed_ends_with_its_owner():
    class Owner:
        pass

    owner = Owner()
    probe = trace_mod.LockTurnProbe(owner)
    probe.PERIOD_S = 0.01
    probe.start()
    assert probe._thread.is_alive()
    del owner
    gc.collect()
    probe._thread.join(5)
    assert not probe._thread.is_alive()


@obs
def test_the_probe_reads_a_held_interpreter_as_lateness():
    """With a thread that never gives the interpreter up of itself the
    probe runs again one switch interval late or more; idle, it reads
    the timer's slack."""

    class Owner:
        pass

    owner, own = Owner(), trace_mod.tracer
    probe = trace_mod.LockTurnProbe(owner)
    probe.PERIOD_S = 0.01
    stop = threading.Event()

    def hold():
        while not stop.is_set():
            _spin(1)

    holder = threading.Thread(target=hold)
    c0, s0, _r = own.stage_counts("runtime.lock_turn")
    holder.start()
    probe.start()
    try:
        time.sleep(0.4)
    finally:
        stop.set()
        holder.join()
        probe.close()
    c1, s1, _r = own.stage_counts("runtime.lock_turn")
    assert c1 - c0 >= 3
    assert s1 > s0  # late at least once
    assert not probe._thread.is_alive()


# -- the benchmark's ten readings --------------------------------------------------


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _layer(name: str) -> dict:
    return json.loads((BENCH / "layers" / f"{name}.json").read_text())


@obs
def test_the_five_parts_are_the_whole_by_construction():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {e["name"]: e for e in bench["per_layer"]}
    whole = _layer("host_cpu_ms_per_query")["args"]
    parts = [s for p in CPU_PARTS for s in _layer(p)["args"]["stages"]]
    assert sorted(parts) == sorted(whole["stages"]) and len(set(parts)) == len(parts)
    # the wall metric's stages and readback: what a launch costs the
    # host includes the call that waits for the device, which costs no CPU
    wall = _layer("host_work_ms_per_query")["args"]["stages"]
    assert set(whole["stages"]) == set(wall) | {"kernel.readback"}
    for name in NEW_LAYERS:
        layer, entry = _layer(name), entries[name]
        assert entry["workloads"] == CELLS, name
        assert (entry["moves"], entry["better"]) == ("queries_per_s", "lower")
        assert {k: layer[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer", "moves")
        }
        args = layer["args"]
        if layer["reader"] == "stage_delta":
            assert all(s in STAGES for s in args["stages"]), name
            if name != "lock_turn_ms":
                assert (args["field"], args["per"]) == ("cpu_ms", "request"), name
        else:
            assert layer["reader"] == "counter_ratio" and args["den_requests"]
    # nothing accepted moved: the entries were the list's tail, and
    # what later PRs add follows them
    names = [e["name"] for e in bench["per_layer"]]
    assert names[55 - len(NEW_LAYERS):55] == NEW_LAYERS


@obs
def test_the_readers_difference_what_the_program_serves(served):
    """The accepted readers over two real snapshots: every new metric
    reads a number, the five parts add up to the whole, and a parent's
    snapshot (no ``cpu_ms``, no ``runtime.thread_*``) reads nothing or
    zero and does not raise."""
    app, get, connect = served
    stage_delta = _load(BENCH / "readers" / "stage_delta.py")
    counter_ratio = _load(BENCH / "readers" / "counter_ratio.py")

    def snapshot():
        return {"metrics": get("/metrics")[1],
                "stages": get("/debug/status")[1]["stages"]}

    before = snapshot()
    for _ in range(30):
        assert get("/info")[0] == 200
    for _ in range(100):  # two samples of the probe at least
        if (trace_mod.tracer.stage_counts("runtime.lock_turn")[0]
                >= before["stages"]["runtime.lock_turn"]["count"] + 2):
            break
        time.sleep(0.05)
    time.sleep(2.5 * ThreadClock.KEEP_S)
    ctx = {"before": before, "after": snapshot(), "records": [None] * 30}

    def read(name):
        layer = _layer(name)
        reader = stage_delta if layer["reader"] == "stage_delta" else counter_ratio
        return reader.read(layer["args"], ctx)

    got = {name: read(name) for name in NEW_LAYERS}
    assert all(isinstance(v, float) for v in got.values()), got
    assert sum(got[p] for p in CPU_PARTS) == pytest.approx(
        got["host_cpu_ms_per_query"], rel=0.01
    )
    assert 0.0 < got["http_cpu_ms"] <= got["host_cpu_ms_per_query"]
    assert got["host_cpu_ms_per_query"] <= got["python_cpu_ms_per_query"] * 1.05
    # thirty requests are a few milliseconds: a scan reads the process
    # after its threads, so between two scans the suite's other threads
    # can move the two differences apart by what they burn meanwhile
    assert got["python_cpu_ms_per_query"] <= got["process_cpu_ms_per_query"] * 1.05 + 0.5
    assert got["yields_per_query"] > 0.0 and got["lock_turn_ms"] >= 0.0
    # the parent serves neither the field nor the counters
    for snap in (ctx["before"], ctx["after"]):
        snap["stages"] = {
            n: {k: v for k, v in s.items() if k != "cpu_ms"} if isinstance(s, dict) else s
            for n, s in snap["stages"].items() if n != "runtime.lock_turn"
        }
        snap["metrics"]["runtime"] = {
            k: v for k, v in snap["metrics"]["runtime"].items() if k.startswith("gc_")
        }
    old = {name: read(name) for name in NEW_LAYERS}
    assert all(v in (None, 0.0) for v in old.values()), old
