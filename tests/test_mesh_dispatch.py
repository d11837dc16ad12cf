"""A coordinator's local datasets are answered by the engine's own
search (ISSUE 47): ``DistributedEngine(workers, local=engine)`` hands
every local dataset to ``engine.search`` on the request's thread,
beside the worker fan-out, and holds no stack, program or route of its
own. Whatever the granularity and shape, its responses are the
engine's, response for response, and the plain reference's
(``sbeacon_tpu/oracle/cpu_oracle.py``).

The conftest forces eight virtual CPU devices, so a multi-dataset
boolean or count is ONE launch of the engine's mesh program here, as on
a host of several chips; the mesh tests skip where only one device is
visible. The one-launch, zero-worker-calls contract also runs in a
pristine subprocess (``mesh_tier_worker.py``) whose counters no sibling
test can have moved.
"""

import dataclasses
import gc
import json
import os
import random
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.telemetry as tel
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.oracle import oracle_search
from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer
from sbeacon_tpu.parallel.mesh import make_mesh
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import MetricsRegistry
from sbeacon_tpu.testing import random_records

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="the engine's mesh stack needs >=2 devices (forced-host CI mesh)",
)

N_SHARDS = 4
SAMPLES = ["S0", "S1"]
LOCAL = [f"d{d}" for d in range(N_SHARDS)]


def _records(d: int, rows: int = 250) -> list:
    return random_records(
        random.Random(40 + d), chrom="1", n=rows, n_samples=len(SAMPLES)
    )


def _shard(ds: str, vcf: str, records):
    return build_index(
        records, dataset_id=ds, vcf_location=vcf, sample_names=SAMPLES
    )


def _engine(shards, **over):
    over.setdefault("response_cache", False)
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(**over)))
    for s in shards:
        eng.add_index(s)
    return eng


def _local_engine(**over):
    """Four datasets on pure defaults (the mesh stack on, as a host of
    several chips serves them), the micro-batcher not waiting."""
    return _engine(
        [_shard(f"d{d}", f"v{d}", _records(d)) for d in range(N_SHARDS)],
        microbatch_wait_ms=0.0,
        **over,
    )


def _payload(datasets, gran="count", include="HIT", **kw):
    return VariantQueryPayload(
        dataset_ids=list(datasets),
        reference_name="1",
        start_min=1,
        start_max=1 << 29,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity=gran,
        include_datasets=include,
        **kw,
    )


def _dicts(responses) -> list:
    return [dataclasses.asdict(r) for r in responses]


def _launches() -> dict:
    return tel.flight_recorder.launches_by_family()


# -- make_mesh device selection -----------------------------------------------


def test_make_mesh_explicit_devices():
    devs = jax.devices()
    m = make_mesh(devices=devs[:1])
    assert m.devices.size == 1
    # explicit ordering is respected, not re-derived from jax.devices()
    if len(devs) >= 2:
        m2 = make_mesh(devices=[devs[1], devs[0]])
        assert list(m2.devices.flat) == [devs[1], devs[0]]


def test_make_mesh_zero_devices_is_loud():
    with pytest.raises(ValueError, match="0 devices"):
        make_mesh(devices=[])


def test_make_mesh_too_many_devices_is_loud():
    with pytest.raises(ValueError, match="only"):
        make_mesh(n_devices=len(jax.devices()) + 1)


# -- the local leg is the engine's own search ---------------------------------

#: the request shapes a coordinator is held to, each as the keywords of
#: its payload (``selected`` restricts the counts to one sample and asks
#: for the carriers' names, so it reads the genotype planes)
SHAPES = {
    "boolean": dict(gran="boolean", include="NONE"),
    "count": dict(gran="count", include="HIT"),
    "record": dict(gran="record", include="HIT"),
    "record-selected": dict(
        gran="record",
        include="ALL",
        include_samples=True,
        selected_samples_only=True,
    ),
    "aggregated": dict(gran="aggregated", include="ALL"),
}
TOPOLOGIES = ("all-local", "beside-a-worker", "one-local")
TAILS = ("base", "delta-tail")


@pytest.fixture(scope="module", params=TAILS)
def fleet(request):
    """A local engine of four datasets (with, under ``delta-tail``, a
    delta standing on d0), one HTTP worker serving a fifth, a
    coordinator over the local engine alone and one over both; and the
    records behind every dataset, for the plain reference."""
    records = {f"d{d}": _records(d) for d in range(N_SHARDS)}
    records["w0"] = random_records(
        random.Random(7), chrom="1", n=150, n_samples=len(SAMPLES)
    )
    eng = _local_engine()
    weng = _engine(
        [_shard("w0", "w0.vcf.gz", records["w0"])],
        use_mesh=False,
        microbatch=False,
    )
    worker = WorkerServer(weng).start_background()
    alone = DistributedEngine([], local=eng)
    beside = DistributedEngine([worker.address], local=eng)
    try:
        assert alone.warmup() > 0
        if request.param == "delta-tail":
            tail = random_records(
                random.Random(77), chrom="1", n=40, n_samples=len(SAMPLES)
            )
            eng.add_delta(_shard("d0", "v0", tail))
            records["d0"] = records["d0"] + tail
        yield {
            "tail": request.param,
            "engine": eng,
            "worker_engine": weng,
            "all-local": (alone, LOCAL),
            "one-local": (alone, ["d0"]),
            "beside-a-worker": (beside, LOCAL + ["w0"]),
            "records": records,
        }
    finally:
        alone.close()
        beside.close()
        worker.shutdown()
        eng.close()
        weng.close()


def _want(records: list, payload, ds: str):
    """The plain reference's answer for one dataset."""
    selected = payload.selected_samples_only
    return oracle_search(
        records,
        first_bp=payload.start_min,
        last_bp=payload.start_max,
        end_min=payload.end_min,
        end_max=payload.end_max,
        reference_bases=payload.reference_bases,
        alternate_bases=payload.alternate_bases,
        variant_type=payload.variant_type,
        requested_granularity=payload.requested_granularity,
        include_details=payload.include_details,
        include_samples=payload.include_samples,
        sample_names=payload.sample_names[ds] if selected else SAMPLES,
        dataset_id=ds,
        chrom_label="1",
        selected_sample_idx=(
            [SAMPLES.index(n) for n in payload.sample_names[ds]]
            if selected
            else None
        ),
    )


@multi_device
@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_coordinators_local_leg_is_the_engines_own_search(
    fleet, shape, topology
):
    """Every granularity and shape, over all the local datasets, over
    the local datasets beside a worker's and over one local dataset,
    with and without a standing delta tail: the coordinator answers
    what ``engine.search`` answers, response for response, and what the
    oracle answers, dataset by dataset."""
    dist, datasets = fleet[topology]
    eng, weng = fleet["engine"], fleet["worker_engine"]
    kw = dict(SHAPES[shape])
    if kw.get("selected_samples_only"):
        kw["sample_names"] = {ds: ["S1"] for ds in datasets}
    payload = _payload(datasets, kw.pop("gran"), kw.pop("include"), **kw)
    local = [ds for ds in datasets if ds != "w0"]
    before = _launches()
    got = dist.search(payload)
    after = _launches()

    # (1) the engine's own search, response for response
    own = eng.search(dataclasses.replace(payload, dataset_ids=local))
    theirs = (
        weng.search(dataclasses.replace(payload, dataset_ids=["w0"]))
        if "w0" in datasets
        else []
    )
    want = sorted(
        own + theirs, key=lambda r: (r.dataset_id, r.vcf_location)
    )
    if topology == "beside-a-worker" and shape == "boolean":
        # a boolean with no per-dataset detail is an OR: a local hit
        # decides it and the worker's leg may be abandoned
        assert any(r.exists for r in own)
        got_local = [r for r in got if r.dataset_id != "w0"]
        assert _dicts(got_local) == _dicts(own)
        assert _dicts(got) in (_dicts(want), _dicts(own))
    else:
        assert _dicts(got) == _dicts(want), (shape, topology)
    assert {r.dataset_id for r in got} >= set(local)

    # (2) the oracle, dataset by dataset (a dataset with a standing
    # tail answers in several responses: their sum is the dataset's)
    for ds in {r.dataset_id for r in got}:
        mine = [r for r in got if r.dataset_id == ds]
        ref = _want(fleet["records"][ds], payload, ds)
        assert any(r.exists for r in mine) == ref.exists, (shape, ds)
        if shape == "boolean":
            continue  # the count stops at the first hit, a response each
        assert sum(r.call_count for r in mine) == ref.call_count, (shape, ds)
        assert (
            sum(r.all_alleles_count for r in mine) == ref.all_alleles_count
        ), (shape, ds)
        assert sorted(v for r in mine for v in r.variants) == sorted(
            ref.variants
        ), (shape, ds)
        if payload.include_samples:
            assert {n for r in mine for n in r.sample_names} == set(
                ref.sample_names
            ), (shape, ds)

    # (3) the route: several local datasets under a boolean or a count
    # are ONE launch of the engine's mesh program over their base rows
    if len(local) > 1 and shape in ("boolean", "count"):
        assert after.get("mesh", 0) - before.get("mesh", 0) == 1
    if topology == "one-local" and fleet["tail"] == "base":
        # one shard is no fan-out: the engine asks its own index
        assert after.get("mesh", 0) == before.get("mesh", 0)


def _resident_by_kind(engine) -> dict:
    """``device.resident_bytes`` as ``/metrics`` serves it, by kind."""
    registry = MetricsRegistry()
    engine.register_metrics(registry)
    by_kind: dict = {}
    by_chip = registry.render_json()["device"]["resident_bytes"]
    for kinds in by_chip.values():
        for kind, nbytes in kinds.items():
            by_kind[kind] = by_kind.get(kind, 0) + nbytes
    return by_kind


def _live_bytes_added(build) -> tuple:
    """(what ``build()`` returned, device bytes alive after it that were
    not before): the least of three readings, so an array another
    module's thread holds for a moment is not counted."""
    gc.collect()
    held = jax.live_arrays()  # held, so no later array takes an id of theirs
    before = {id(a) for a in held}
    built = build()
    readings = []
    for _ in range(3):
        gc.collect()
        readings.append(
            sum(a.nbytes for a in jax.live_arrays() if id(a) not in before)
        )
        time.sleep(0.05)
    return built, min(readings)


@multi_device
def test_a_coordinator_holds_no_stack_of_its_own():
    """After ``warmup()`` a coordinator with a local engine holds on the
    devices what the plain engine holds: ``device.resident_bytes`` by
    kind is the engine's, and no array is alive beside the engine's."""

    def plain():
        eng = _local_engine()
        assert eng.warmup() > 0
        return eng

    def coordinated():
        eng = _local_engine()
        dist = DistributedEngine([], local=eng)
        assert dist.warmup() > 0
        return eng, dist

    eng, plain_bytes = _live_bytes_added(plain)
    try:
        plain_kinds = _resident_by_kind(eng)
    finally:
        eng.close()
        del eng
    (eng, dist), coordinated_bytes = _live_bytes_added(coordinated)
    try:
        assert _resident_by_kind(dist) == plain_kinds
        assert set(plain_kinds) >= {"stack"} and plain_kinds["stack"] > 0
        assert coordinated_bytes == plain_bytes > 0
        assert dist.search(_payload(LOCAL))  # and it serves
    finally:
        dist.close()
        eng.close()


# -- parity, route by route ---------------------------------------------------


@multi_device
def test_local_leg_parity_across_granularities():
    """The coordinator over an engine on its mesh stack against a plain
    engine with the mesh off and no batcher: another route, the same
    responses."""
    eng = _local_engine()
    eng_ref = _local_engine(use_mesh=False, microbatch=False)
    dist = DistributedEngine([], local=eng)
    try:
        assert dist.warmup() > 0
        searches = eng.mesh_searches
        for gran, include in [
            ("boolean", "NONE"),
            ("count", "HIT"),
            ("record", "HIT"),
            ("aggregated", "ALL"),
        ]:
            pay = _payload(LOCAL, gran, include)
            assert _dicts(dist.search(pay)) == _dicts(eng_ref.search(pay)), (
                gran, include,
            )
        assert eng.mesh_searches >= searches + 2
    finally:
        dist.close()
        eng.close()
        eng_ref.close()


@multi_device
def test_local_leg_plane_parity_suite():
    """Selected-samples and sample-extraction shapes at every
    granularity: the coordinator's answers are the plain engine's."""
    eng = _local_engine()
    eng_ref = _local_engine(use_mesh=False, microbatch=False)
    dist = DistributedEngine([], local=eng)
    try:
        dist.warmup()
        for gran in ("boolean", "count", "record"):
            for mode in ("selected", "extract"):
                kw = (
                    dict(
                        selected_samples_only=True,
                        sample_names={d: ["S1"] for d in LOCAL},
                    )
                    if mode == "selected"
                    else dict(include_samples=True)
                )
                pay = _payload(LOCAL, gran, "ALL", **kw)
                assert _dicts(dist.search(pay)) == _dicts(
                    eng_ref.search(pay)
                ), (gran, mode)
    finally:
        dist.close()
        eng.close()
        eng_ref.close()


@multi_device
def test_plane_shapes_ride_one_launch_an_owner_chip(monkeypatch):
    """A sample-extraction request over cohorts on several chips is one
    match+planes launch an OWNER chip (``device.launches{plane}``; the
    chip's index family forced on the CPU, where placement gives every
    dataset a chip of its own), with the answers of the plain engine."""
    eng_ref = _local_engine(use_mesh=False, microbatch=False)
    monkeypatch.setattr(
        engine_mod,
        "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    rec = tel.DeviceFlightRecorder()
    monkeypatch.setattr(tel, "flight_recorder", rec)
    eng = _local_engine()
    dist = DistributedEngine([], local=eng)
    try:
        dist.warmup()
        owners = {row["chip"] for row in eng.placement_table()}
        pay = _payload(LOCAL, "record", "ALL", include_samples=True)
        want = eng_ref.search(pay)
        before = rec.launches_by_family().get("plane", 0)
        got = dist.search(pay)
        assert (
            rec.launches_by_family().get("plane", 0) - before
            == len(owners)
            > 1
        )
        assert len(got) == N_SHARDS
        assert all(r.sample_names for r in got if r.exists)
        assert _dicts(got) == _dicts(want)
        assert rec.fallbacks_by_site() == {}
    finally:
        dist.close()
        eng.close()
        eng_ref.close()


@multi_device
def test_a_query_over_local_and_worker_datasets_runs_both_legs():
    """Local datasets ride the engine's one mesh launch on the request's
    thread; a dataset only a worker serves keeps the pooled-HTTP
    scatter: one query, both legs, one merged response set."""
    weng = _engine(
        [_shard("w0", "w0.vcf.gz", random_records(
            random.Random(7), chrom="1", n=150, n_samples=2
        ))],
        use_mesh=False,
        microbatch=False,
    )
    worker = WorkerServer(weng).start_background()
    eng = _local_engine()
    dist = DistributedEngine([worker.address], local=eng)
    try:
        dist.warmup()
        searches, mesh = eng.mesh_searches, _launches().get("mesh", 0)
        got = dist.search(_payload(LOCAL + ["w0"]))
        assert [r.dataset_id for r in got] == ["d0", "d1", "d2", "d3", "w0"]
        assert eng.mesh_searches == searches + 1
        assert _launches().get("mesh", 0) == mesh + 1
    finally:
        dist.close()
        worker.shutdown()
        eng.close()
        weng.close()


# -- pristine-process single-launch contract (subprocess) ---------------------

WORKER = Path(__file__).with_name("mesh_tier_worker.py")


@pytest.mark.timeout(600)
def test_pod_contract_in_subprocess(tmp_path):
    """A fresh process with XLA_FLAGS-forced devices runs the whole
    contract with counters nothing else has moved: an all-local boolean
    is ONE launch, of the ``mesh`` family, with zero coordinator-to-
    worker calls, and the answers are a plain engine's."""
    out = tmp_path / "out.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo = str(WORKER.parent.parent)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(WORKER), str(out)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=repo,
        timeout=540,
    )
    assert proc.returncode == 0, f"worker failed:\n{proc.stdout[-2000:]}"
    doc = json.loads(out.read_text())
    assert doc["devices"] >= 2
    assert doc["mesh_launches"] == 1
    assert doc["total_launches"] == 1
    assert doc["launches_by_family"] == {"mesh": 1}
    assert doc["worker_http_calls"] == 0
    assert doc["transport_stats_unchanged"] is True
    assert doc["mesh_searches"] == 1
    assert doc["exists"] is True
    assert doc["parity_ok"] is True
