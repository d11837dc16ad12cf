"""Device-plane flight recorder (ISSUE 14): the /device/status golden
schema, launch-ring bounds, padding-waste math, the compile-event
tracker's warmup-coverage contract, the HBM ledger surface, and the
device.launch trace graft. obs-marked, tier-1 safe (8 forced host
devices via conftest)."""

import random
import threading

import pytest

from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import DeviceIndex, QuerySpec
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import (
    DeviceFlightRecorder,
    RequestContext,
    journal,
    request_context,
)
from sbeacon_tpu.testing import random_records

obs = pytest.mark.obs

N_SHARDS = 2


def _build_engine():
    # the mesh stack on, as a host of several chips serves (the
    # conftest's eight virtual devices): a multi-dataset count is the
    # ``mesh`` family, a one-dataset one ``fused``
    cfg = BeaconConfig(engine=EngineConfig(microbatch_wait_ms=0.0))
    eng = VariantEngine(cfg)
    for d in range(N_SHARDS):
        rng = random.Random(40 + d)
        eng.add_index(
            build_index(
                random_records(rng, chrom="1", n=120, n_samples=2),
                dataset_id=f"d{d}",
                vcf_location=f"v{d}",
                sample_names=["S0", "S1"],
            )
        )
    return eng


def _payload(**over):
    kw = dict(
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
    kw.update(over)
    return VariantQueryPayload(**kw)


@pytest.fixture(scope="module")
def warm_stack():
    """One warmed serving stack under a FRESH flight recorder (the
    process global accumulates across the whole pytest run otherwise):
    the engine with its fused and mesh stacks, all warmed INSIDE
    warmup phases, plus the app serving /device/status."""
    import sbeacon_tpu.telemetry as tel
    from sbeacon_tpu.api import BeaconApp

    # one swap point: every seam (kernels, app, debug status) resolves
    # telemetry.flight_recorder at call time
    rec = DeviceFlightRecorder(ring_size=256)
    old = tel.flight_recorder
    tel.flight_recorder = rec
    eng = _build_engine()
    eng.warmup()
    app = BeaconApp(engine=eng)
    try:
        yield app, eng, rec
    finally:
        app.close()
        eng.close()
        tel.flight_recorder = old


# -- recorder unit: ring bounds + padding-waste math --------------------------


@obs
def test_launch_ring_bounds_and_eviction():
    rec = DeviceFlightRecorder(ring_size=4)
    for k in range(10):
        rec.record_launch(
            "fused",
            seam="kernel",
            tier=8,
            specs_real=1 + k % 3,
            specs_padded=8,
        )
    snap = rec.snapshot()
    assert snap["ring"]["size"] == 4
    assert snap["ring"]["recorded"] == 10
    entries = snap["ring"]["entries"]
    assert [e["seq"] for e in entries] == [7, 8, 9, 10]  # oldest evicted
    # counters survive eviction (lifetime, not ring-bounded)
    assert snap["total"] == 10
    # a stage note for an evicted seq must be a silent no-op
    rec.note_stage(1, fetch_ms=1.0)
    # shrink-on-configure trims the ring
    rec.configure(ring_size=2)
    assert len(rec.snapshot()["ring"]["entries"]) == 2


@obs
def test_padding_waste_math_at_tier_boundaries():
    rec = DeviceFlightRecorder()
    # the ISSUE 14 example: 9 specs padded to tier 64
    rec.record_launch(
        "fused", seam="kernel", tier=64, specs_real=9, specs_padded=64
    )
    worst = rec.worst_pad_waste()
    assert worst == {"family": "fused", "tier": 64, "waste": 0.8594}
    # an exactly-full tier wastes nothing; the family ratio pools both
    rec.record_launch(
        "fused", seam="kernel", tier=64, specs_real=64, specs_padded=64
    )
    by_tier = rec.snapshot()["padWaste"]["byTier"]
    assert by_tier["fused:64"] == pytest.approx(1 - 73 / 128, abs=1e-3)
    assert rec.pad_waste_by_family()["fused"] == by_tier["fused:64"]
    # a mesh launch: one query over 4 real datasets of a stack padded
    # to 8 dataset slots -> half the evaluated slots were inert fillers
    rec.record_launch(
        "mesh",
        seam="mesh",
        tier=1,
        specs_real=4,
        specs_padded=8,
        evaluated_pairs=8,
    )
    assert rec.pad_waste_by_family()["mesh"] == 0.5
    assert rec.mesh_launches == 1
    assert rec.evaluated_pairs == 8


@obs
def test_recorder_seam_counters_feed_module_properties(monkeypatch):
    import sbeacon_tpu.telemetry as tel

    rec = DeviceFlightRecorder()
    monkeypatch.setattr(tel, "flight_recorder", rec)
    import sbeacon_tpu.ops.kernel as kernel_mod
    import sbeacon_tpu.ops.scatter_kernel as scatter_mod
    import sbeacon_tpu.parallel.mesh as mesh_mod

    rec.record_launch(
        "fused", seam="kernel", tier=8, specs_real=1, specs_padded=8
    )
    rec.record_launch(
        "plane",
        seam="mesh",
        tier=8,
        specs_real=2,
        specs_padded=8,
        evaluated_pairs=64,
    )
    rec.record_launch(
        "scatter", seam="scatter", tier=64, specs_real=3, specs_padded=64
    )
    assert kernel_mod.N_LAUNCHES == 1
    assert mesh_mod.N_LAUNCHES == 1
    assert rec.evaluated_pairs == 64
    assert scatter_mod.N_DISPATCHES == 1
    with pytest.raises(AttributeError):
        mesh_mod.N_NO_SUCH_COUNTER


# -- /device/status golden schema + reconciliation ----------------------------

GOLDEN_DEVICE_KEYS = {
    "total",
    "byFamily",
    "targetsByFamily",
    "evaluatedPairs",
    "fetchedBytes",
    "donatedBuffers",
    "queryUploads",
    "planeGatherBytes",
    "fallbacks",
    "ring",
    "padWaste",
    "compiles",
    "hbm",
    "stacks",
    "time",
}

GOLDEN_RING_ENTRY_KEYS = {
    "seq",
    "family",
    "tier",
    "specs",
    "padded",
    "padWaste",
    "evaluatedPairs",
    "launchMs",
    "time",
}

GOLDEN_HBM_KEYS = {
    "residentBytes",
    "reservedBytes",
    "reservedTokens",
    "budgetBytes",
    "headroomBytes",
    "fullestChipBytes",
    "stale",
}


@obs
def test_device_status_golden_schema_and_reconciliation(warm_stack):
    app, eng, rec = warm_stack
    # at least one serving-path launch of each stack recorded: the two
    # datasets in one mesh launch, one dataset through the batcher
    eng.search(_payload())
    eng.search(_payload(dataset_ids=["d0"]))
    status, doc = app.handle("GET", "/device/status")
    assert status == 200
    assert set(doc) == GOLDEN_DEVICE_KEYS
    assert doc["total"] >= 2 and doc["byFamily"].get("fused", 0) >= 1
    assert doc["byFamily"].get("mesh", 0) >= 1
    entries = doc["ring"]["entries"]
    assert entries and all(
        GOLDEN_RING_ENTRY_KEYS <= set(e) for e in entries
    )
    # the serving micro-batcher path attaches its encode stage and the
    # fetcher its readback to the SAME record the kernel seam wrote
    assert any("encodeMs" in e and "fetchMs" in e for e in entries)
    # padding waste reconciles with the launch ring (nothing evicted
    # at this volume: the ring IS the lifetime history)
    fused = [e for e in entries if e["family"] == "fused"]
    real = sum(e["specs"] for e in fused)
    padded = sum(e["padded"] for e in fused)
    assert doc["padWaste"]["byFamily"]["fused"] == pytest.approx(
        1 - real / padded, abs=1e-3
    )
    assert set(doc["hbm"]) == GOLDEN_HBM_KEYS
    # the HBM numbers reconcile with the engine's own ledger: the
    # budget is a chip's, and the gates read the fullest chip
    by_chip: dict = {}
    for _k, _s, p in eng.index_snapshot():
        if p is not None:
            by_chip[str(p.device)] = (
                by_chip.get(str(p.device), 0) + p.nbytes_hbm()
            )
    assert doc["hbm"]["fullestChipBytes"] == max(by_chip.values()) > 0
    assert (
        doc["hbm"]["headroomBytes"]
        == doc["hbm"]["budgetBytes"] - doc["hbm"]["fullestChipBytes"]
    )
    # stack states: the fused stack with identity and age, and no
    # stack beside the engine's own
    assert set(doc["stacks"]) == {"fused"}
    assert doc["stacks"]["fused"]["built"] is True
    assert doc["stacks"]["fused"]["fingerprint"]
    # ... and the mesh stack's slices, by chip, beside tiles and planes
    assert any(kind == "stack" for _chip, kind in eng.resident_bytes())
    # compile cache vs warmup shape set: everything so far was warmed
    assert doc["compiles"]["enabled"] is True
    assert doc["compiles"]["warmupShapes"]
    # the device.* series render through /metrics
    _, metrics = app.handle("GET", "/metrics")
    assert metrics["device"]["launches"]["fused"] >= 1
    assert "pad_waste" in metrics["device"]
    # what the planes hold, to the byte: the gauge hosts are sized by
    held = sum(
        int(a.nbytes)
        for _k, _s, p in eng.index_snapshot()
        if p is not None
        for a in p.planes()
    )
    assert metrics["device"]["plane_resident_bytes"] == held > 0
    assert held == doc["hbm"]["residentBytes"]


@obs
def test_device_status_answers_during_stack_rebuild(warm_stack):
    """Acceptance: /device/status must answer while a publish/rebuild
    holds the engine's publish lock — the HBM ledger serves its last
    snapshot flagged stale instead of queueing behind the lock."""
    app, eng, _rec = warm_stack
    app.handle("GET", "/device/status")  # prime the ledger cache
    assert eng._mesh_lock.acquire(timeout=5)
    try:
        done = {}

        def probe():
            done["resp"] = app.handle("GET", "/device/status")

        t = threading.Thread(target=probe, daemon=True)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive(), (
            "/device/status blocked on the publish lock"
        )
    finally:
        eng._mesh_lock.release()
    status, doc = done["resp"]
    assert status == 200
    assert doc["hbm"]["stale"] is True
    # with the lock free again the snapshot refreshes
    _, doc = app.handle("GET", "/device/status")
    assert doc["hbm"]["stale"] is False


# -- warmup-coverage regression (ISSUE 14 satellite) --------------------------


@obs
def test_warm_paths_record_zero_compile_events(warm_stack):
    """The perf-smoke warm paths — cached repeat, fused serving, the
    engine's mesh launch, the plane shapes — must record ZERO
    device.compile events end-to-end after warmup: every program they
    dispatch was stamped during a warmup phase."""
    _app, eng, rec = warm_stack
    seq0 = journal.last_seq()
    c0 = rec.mid_request_compiles()
    f0 = rec.launches_by_family()
    # the mesh launch (two datasets) + the cached repeat
    eng.search(_payload(no_response_cache=True))
    eng.search(_payload())
    eng.search(_payload())
    # the fused serving path (one dataset, through the batcher)
    eng.search(_payload(dataset_ids=["d1"], no_response_cache=True))
    # the plane shapes: carriers' names, and counts over a selection
    names = {f"d{d}": ["S1"] for d in range(N_SHARDS)}
    for kw in (
        dict(include_samples=True),
        dict(selected_samples_only=True, sample_names=names),
    ):
        eng.search(
            _payload(
                requested_granularity="record",
                include_datasets="ALL",
                no_response_cache=True,
                **kw,
            )
        )
    f1 = rec.launches_by_family()
    for family in ("mesh", "fused", "plane"):
        assert f1.get(family, 0) > f0.get(family, 0), family
    assert rec.mid_request_compiles() - c0 == 0
    assert journal.events(since=seq0, kind="device.compile") == []


@obs
def test_warmup_ladder_parity_lint_green_on_warm_stack(warm_stack):
    """ISSUE 17 satellite: after warmup, EVERY rung of the active
    TierLadder is covered by a warmup-phase compile for the fused
    family (the batcher pads its launches to a rung), and the engine's
    mesh program at the one batch it serves (a request a launch). An
    uncovered cell is a batch shape that would pay a mid-request
    compile, which test_warm_paths_record_zero_compile_events would
    only catch for the specific shapes it happens to dispatch."""
    import sys
    from pathlib import Path

    sys.path.insert(
        0, str(Path(__file__).resolve().parent.parent / "tools")
    )
    try:
        from check_launch_recording import (
            expected_warm_rungs,
            lint_warmup_ladder,
        )
    finally:
        sys.path.pop(0)
    from sbeacon_tpu.ops.kernel import active_ladder

    _app, _eng, rec = warm_stack
    expected = expected_warm_rungs(active_ladder(), families=("fused",))
    expected["mesh"] = (1,)
    errs = lint_warmup_ladder(rec.compile_snapshot(), expected)
    assert errs == [], errs


@obs
def test_unwarmed_shape_is_one_named_mid_request_compile(warm_stack):
    """A deliberately un-warmed program shape must produce EXACTLY ONE
    device.compile event, detected within the same request (the event
    carries the request's trace id plus shape + duration), and the
    /debug/status diagnosis must name it."""
    from sbeacon_tpu.ops.kernel import run_queries

    app, eng, rec = warm_stack
    seq0 = journal.last_seq()
    c0 = rec.mid_request_compiles()
    shard = eng._indexes[sorted(eng._indexes)[0]][0]
    # a novel pad_unit means a novel padded row count — a program
    # signature no warmup has ever touched
    fresh = DeviceIndex(shard, pad_unit=4096)
    ctx = RequestContext(route="g_variants")
    with request_context(ctx):
        run_queries(fresh, [QuerySpec("1", 1, 1, 1, 2)] * 3)
        # the SAME shape again: the compile already happened, so a
        # repeat must not double-count
        run_queries(fresh, [QuerySpec("1", 1, 1, 1, 2)] * 3)
    assert rec.mid_request_compiles() - c0 == 1
    events = journal.events(since=seq0, kind="device.compile")
    assert len(events) == 1
    evt = events[0]
    assert evt["traceId"] == ctx.trace_id  # same-request detection
    assert evt["data"]["durationMs"] >= 0
    assert "4096" in evt["data"]["shape"]
    status, dbg = app.handle("GET", "/debug/status")
    assert status == 200
    diag = dbg["diagnosis"]
    assert diag["midRequestCompiles"] >= 1
    assert diag["lastMidRequestCompile"] and (
        "4096" in diag["lastMidRequestCompile"]
    )
    assert diag["worstPadWaste"] is not None
    assert dbg["device"]["launches"]["total"] >= 1


# -- HBM ledger tokens --------------------------------------------------------


@obs
def test_hbm_ledger_shows_an_upload_in_flight_and_its_release(monkeypatch):
    """A plane upload holds a reservation against its owner chip from
    the budget gate until the planes are published: in flight the
    ledger shows one token and the bytes the gate reserved, published
    they count as resident and the token is gone."""
    import sbeacon_tpu.ops.plane_kernel as plane_mod

    rng = random.Random(5)
    shard = build_index(
        random_records(rng, chrom="1", n=120, n_samples=2),
        dataset_id="up",
        vcf_location="up.vcf.gz",
        sample_names=["S0", "S1"],
    )
    eng = VariantEngine(BeaconConfig())
    in_flight = {}

    class Uploading(plane_mod.PlaneDeviceIndex):
        def __init__(self, *args, **kw):
            # the ledger as a /device/status poll would see it mid-upload
            in_flight.update(eng.plane_ledger())
            super().__init__(*args, **kw)

    monkeypatch.setattr(plane_mod, "PlaneDeviceIndex", Uploading)
    try:
        led = eng.plane_ledger()
        assert led["reservedTokens"] == 0 and led["reservedBytes"] == 0
        assert led["stale"] is False
        eng.add_index(shard)
        assert in_flight["reservedTokens"] == 1
        assert in_flight["reservedBytes"] > 0
        assert in_flight["residentBytes"] == 0
        assert in_flight["fullestChipBytes"] == in_flight["reservedBytes"]
        assert (
            in_flight["headroomBytes"]
            == in_flight["budgetBytes"] - in_flight["reservedBytes"]
        )
        led = eng.plane_ledger()
        assert led["reservedTokens"] == 0 and led["reservedBytes"] == 0
        assert led["residentBytes"] == led["fullestChipBytes"] > 0
    finally:
        eng.close()


# -- trace graft --------------------------------------------------------------


@obs
def test_trace_graft_shows_device_launch_span_with_tier():
    """With tracing on, a kernel launch grafts a device.launch child
    span (family + tier + specs) into the request's span tree — the
    in-process twin of the PR 12 worker-span graft."""
    from sbeacon_tpu.ops.kernel import run_queries
    from sbeacon_tpu.utils.trace import tracer

    rng = random.Random(7)
    shard = build_index(
        random_records(rng, chrom="1", n=60, n_samples=2),
        dataset_id="tg",
        vcf_location="tg.vcf.gz",
        sample_names=["S0", "S1"],
    )
    dindex = DeviceIndex(shard)
    tracer.enable()
    try:
        tracer.reset()
        run_queries(dindex, [QuerySpec("1", 1, 1, 1, 2)] * 3)
        trees = tracer.recent_trees()
    finally:
        tracer.disable()
        tracer.reset()
    launches = [
        sp
        for tree in trees
        for sp in _flatten(tree)
        if sp["name"] == "device.launch"
    ]
    assert launches, f"no device.launch span grafted: {trees}"
    meta = launches[-1]["meta"]
    assert meta["family"] == "fused"
    assert meta["tier"] == 8  # 3 specs pad to the 8 tier
    assert meta["specs"] == 3


def _flatten(tree: dict):
    yield tree
    for child in tree.get("children", ()):
        yield from _flatten(child)
