"""Many small cohorts with their genotypes on one chip (``mdsp``): sixteen
1000-sample datasets whose planes are resident four rows to a lane row.

The plane budget admits all sixteen where one row to a lane row it
admitted eight; a filtered record request over all of them through
``app.handle`` is sixteen match+planes launches from the scatter pool,
each dataset answered as the per-record loop answers it, and the fan-out's
counter, wait stage and fill gauge read what happened. CPU, the chip's
index family forced as tests/test_chip_bringup.py does; the word-for-word
parity of the packed programs is tests/test_plane_kernel.py's, their
compile at ``mdsp``'s real shapes tests/test_chip_compile.py's.
"""

import dataclasses

import jax
import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import (
    VariantEngine,
    host_match_rows,
    materialize_response_loop,
)
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.plane_kernel import PlaneDeviceIndex, resident_shape
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import flight_recorder
from sbeacon_tpu.testing import synthetic_shard
from sbeacon_tpu.utils.trace import tracer

N_DATASETS = 16
N_SAMPLES = 1000  # 32 plane words: four rows to a lane row
N_ROWS = 1500
TERMS = tuple(f"MONDO:{5000 + t:07d}" for t in range(25))


def _shard(d: int):
    # one seed: sixteen cohorts of one shape share their compiled programs
    return synthetic_shard(
        N_ROWS, n_samples=N_SAMPLES, seed=31, dataset_id=f"mc{d}",
        chroms=["1"], with_gt_planes=True, plane_density=0.25,
    )


def _one_chip(patch) -> None:
    """The chip's index family, and ONE local device of the suite's
    eight: the node under test is a chip with sixteen keys on it."""
    patch.setattr(
        engine_mod, "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    first = jax.local_devices()[:1]
    patch.setattr(jax, "local_devices", lambda *a, **kw: first)


@pytest.fixture
def scatter_family(monkeypatch):
    _one_chip(monkeypatch)


def _fill(shard) -> float:
    """Per cent of a resident 32-word plane that is the plane's own
    words: four rows fill a lane row, the rows in whole steps of 128."""
    return 100.0 * shard.n_rows / (-(-shard.n_rows // 128) * 128)


def _host_planes() -> int:
    return flight_recorder.fallbacks_by_site().get("host_planes", 0)


def test_sixteen_packed_planes_pass_a_budget_that_declined_eight(scatter_family):
    """A budget of eight planes held one row to a lane row (``[n, 128]``,
    what a 1000-sample plane took before): all sixteen are resident, none
    declined, and a request that reads them counts no fall-back."""
    shards = [_shard(d) for d in range(N_DATASETS)]
    lane_rows, lanes = resident_shape(shards[0].n_rows, 32)
    assert (lane_rows, lanes) == (-(-shards[0].n_rows // 128) * 32, 128)
    unpacked = shards[0].n_rows * 128 * 4
    packed = PlaneDeviceIndex.estimate_hbm(shards[0])
    assert packed == lane_rows * 512 and packed < unpacked / 3.9
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(
        use_mesh=False, plane_hbm_budget_gb=8.5 * unpacked / 1e9,
    )))
    try:
        for shard in shards:
            eng.add_index(shard)
        planes = [p for _k, _s, p in eng.index_snapshot()]
        assert all(p is not None for p in planes) and len(planes) == N_DATASETS
        assert not eng._planes_declined
        for p in planes:
            assert p.nbytes_hbm() == packed == sum(
                int(a.nbytes) for a in p.planes())
        assert eng.plane_ledger()["residentBytes"] == N_DATASETS * packed
        assert N_DATASETS * packed < 8.5 * unpacked < N_DATASETS * unpacked
        assert eng.plane_fill() == {"0": pytest.approx(_fill(shards[0]))}
        before = _host_planes()
        names = shards[0].meta["sample_names"]
        pos = int(shards[0].cols["pos"][N_ROWS // 2])
        got = eng.search(VariantQueryPayload(
            dataset_ids=[], reference_name="1", start_min=max(1, pos - 5000),
            start_max=pos + 5000, end_min=1, end_max=1 << 30,
            alternate_bases="N", include_datasets="HIT",
            requested_granularity="record", include_samples=True,
            selected_samples_only=True,
            sample_names={f"mc{d}": names[d::25] for d in range(N_DATASETS)},
        ))
        assert len(got) == N_DATASETS and any(r.exists for r in got)
        assert _host_planes() == before
    finally:
        eng.close()


def _submission(ds: str, d: int, samples: list) -> dict:
    """Individual i of dataset d carries term (i + d) mod 25: every
    dataset resolves a term to forty samples of its own."""
    idx = range(len(samples))
    return {
        "datasetId": ds, "assemblyId": "GRCh38", "vcfLocations": [],
        "dataset": {"name": ds, "description": "packed planes"}, "index": True,
        "individuals": [
            {"id": f"{ds}-I{i}", "sex": {"id": "NCIT:C16576", "label": "-"},
             "diseases": [{"diseaseCode": {"id": TERMS[(i + d) % len(TERMS)]}}]}
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
def node(tmp_path_factory):
    """Sixteen planed 1000-sample datasets with every sample's metadata
    behind ``app.handle``; ``engine.search`` tapped: (app, shards, calls)."""
    patch = pytest.MonkeyPatch()
    _one_chip(patch)
    config = BeaconConfig.from_env(tmp_path_factory.mktemp("packed_root"))
    config = dataclasses.replace(
        config, engine=dataclasses.replace(config.engine, use_mesh=False)
    )
    app = BeaconApp(config)
    shards = [_shard(d) for d in range(N_DATASETS)]
    for d, shard in enumerate(shards):
        app.engine.add_index(shard)
        st, doc = app.handle(
            "POST", "/submit",
            body=_submission(f"mc{d}", d, shard.meta["sample_names"]),
        )
        assert st == 200, doc
    calls = []
    search = app.engine.search

    def tapped(payload):
        responses = search(payload)
        calls.append((payload, responses))
        return responses

    app.engine.search = tapped
    try:
        yield app, shards, calls
    finally:
        app.close()
        app.engine.close()
        patch.undo()


@pytest.mark.parametrize(
    "term,width,descendants",
    [(3, 40, False), (11, 400_000, True), (24, 3_000_000, None)],
)
def test_a_filtered_record_request_over_sixteen_datasets(
    node, term, width, descendants
):
    """Each dataset answers as the per-record loop does over ITS forty
    samples; the request is ONE ``plane`` launch of sixteen targets on
    its own thread (one launch group: the chip's sixteen datasets), no
    pool task (``engine.fanout_targets`` and ``engine.pool_wait`` stand
    still), no fall-back."""
    app, shards, calls = node
    c = shards[0].cols
    snv = np.flatnonzero((c["ref_len"] == 1) & (c["alt_len"] == 1) & (c["ac"] > 0))
    pos = int(c["pos"][snv[len(snv) // 3 + term]])
    flt = {"id": TERMS[term], "scope": "individuals"}
    if descendants is not None:
        flt["includeDescendantTerms"] = descendants
    body = {"query": {
        "requestedGranularity": "record",
        "includeResultsetResponses": "HIT",
        "requestParameters": {
            "assemblyId": "GRCh38", "referenceName": "1",
            "start": [max(0, pos - width)], "end": [pos + width],
            "alternateBases": "N",
        },
        "filters": [flt],
        "pagination": {"skip": 0, "limit": 100},
    }}
    del calls[:]
    targets = app.engine.fanout_targets
    waits = tracer.stage_counts("engine.pool_wait")[0]
    launches = flight_recorder.launches_by_family().get("plane", 0)
    slots = flight_recorder.launch_targets_by_family().get("plane", 0)
    fallbacks = sum(flight_recorder.fallbacks_by_site().values())
    st, doc = app.handle("POST", "/g_variants", body=body)
    assert st == 200, doc
    assert doc["responseSummary"]["exists"] is True
    assert app.engine.fanout_targets == targets
    assert tracer.stage_counts("engine.pool_wait")[0] == waits
    assert flight_recorder.launches_by_family()["plane"] - launches == 1
    assert (
        flight_recorder.launch_targets_by_family()["plane"] - slots
        == N_DATASETS
    )
    assert sum(flight_recorder.fallbacks_by_site().values()) == fallbacks
    _st, metrics = app.handle("GET", "/metrics")
    assert metrics["engine"]["fanout_targets"] == app.engine.fanout_targets
    assert (
        metrics["device"]["launch_targets"]["plane"]
        == flight_recorder.launch_targets_by_family()["plane"]
    )
    assert metrics["device"]["plane_fill"] == {
        "0": pytest.approx(_fill(shards[0]))}

    (payload, responses), = calls
    assert payload.selected_samples_only and len(responses) == N_DATASETS
    spec = QuerySpec(
        payload.reference_name, payload.start_min, payload.start_max,
        payload.end_min, payload.end_max,
        alternate_bases=payload.alternate_bases,
    )
    by_dataset = {r.dataset_id: r for r in responses}
    for d, shard in enumerate(shards):
        ds = f"mc{d}"
        got = by_dataset[ds]
        names = shard.meta["sample_names"]
        want_sel = [i for i in range(N_SAMPLES) if (i + d) % 25 == term]
        # carriers come back as positions in the server's own order of
        # the selection
        sel = [names.index(n) for n in payload.sample_names[ds]]
        assert sorted(sel) == want_sel and len(sel) == 40
        want = materialize_response_loop(
            shard, host_match_rows(shard, spec, ref_wildcard=True), payload,
            chrom_label="1", dataset_id=ds, selected_idx=sel,
        )
        assert (got.exists, got.call_count, got.all_alleles_count) == (
            want.exists, want.call_count, want.all_alleles_count)
        assert got.variants == want.variants
        assert got.sample_indices == want.sample_indices
    assert any(r.exists for r in responses)
