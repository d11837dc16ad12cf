"""The engine's mesh stack under a many-dataset deployment (``mds4``'s
shape at a small size): a served ``VariantEngine`` over ten seeded
datasets of UNLIKE row counts on the eight forced CPU devices, so the
stack is padded both ways (rows to the longest dataset, datasets from
10 to 16), held to ``sbeacon_tpu/oracle/cpu_oracle.py`` per dataset and
in aggregate; the launch's stages, its flight-recorder record and the
fan-out's counters as the benchmark's metrics read them; the skip
counter; four request threads launching the collective program at once.
"""

import random
import threading

import pytest

from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.oracle import oracle_search
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import flight_recorder
from sbeacon_tpu.testing import random_records
from sbeacon_tpu.utils.trace import tracer

SAMPLES = ["S0", "S1", "S2"]
N_DATASETS = 10
CHROM = "7"


def _records(d: int) -> list:
    # unlike sizes: 150, 187, 224, ... records
    return random_records(
        random.Random(9100 + d), chrom=CHROM, n=150 + 37 * d, n_samples=len(SAMPLES)
    )


def _engine(**eng_over) -> tuple:
    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(response_cache=False, **eng_over))
    )
    recs = {}
    for d in range(N_DATASETS):
        recs[f"d{d}"] = _records(d)
        eng.add_index(
            build_index(
                recs[f"d{d}"], dataset_id=f"d{d}", vcf_location=f"v{d}.vcf.gz",
                sample_names=SAMPLES,
            )
        )
    eng.warmup()
    return eng, recs


@pytest.fixture(scope="module")
def served():
    eng, recs = _engine()
    yield eng, recs
    eng.close()


def _payload(**kw) -> VariantQueryPayload:
    base = dict(
        dataset_ids=[], reference_name=CHROM, end_min=0, end_max=10**9,
        reference_bases="N", alternate_bases="N", include_datasets="ALL",
        requested_granularity="count",
    )
    base.update(kw)
    return VariantQueryPayload(**base)


def _snv(recs: list, k: int):
    """The k-th record with a single-base alternate that has calls."""
    hits = [
        (r, a) for r in recs
        for a, ac in zip(r.alts, r.effective_ac())
        if len(r.ref) == 1 and len(a) == 1 and a in "ACGT" and ac > 0
    ]
    return hits[k % len(hits)]


def _shape(recs: dict, shape: str, k: int = 0) -> dict:
    r, alt = _snv(recs[f"d{(3 + k) % N_DATASETS}"], k)
    if shape == "point":
        return dict(start_min=r.pos, start_max=r.pos, reference_bases=r.ref,
                    alternate_bases=alt)
    if shape == "range":
        # 1-10 kb with alternate N, as the benchmark's fanout-all sends
        return dict(start_min=max(1, r.pos - 4000), start_max=r.pos + 4000)
    if shape == "n_alternate":
        return dict(start_min=r.pos, start_max=r.pos, reference_bases=r.ref,
                    alternate_bases="N")
    if shape == "miss":
        top = max(x.pos for rs in recs.values() for x in rs)
        return dict(start_min=top + 10_000, start_max=top + 20_000)
    raise ValueError(shape)


def _want(recs: dict, payload) -> dict:
    return {
        ds: oracle_search(
            rs,
            first_bp=payload.start_min, last_bp=payload.start_max,
            end_min=payload.end_min, end_max=payload.end_max,
            reference_bases=payload.reference_bases,
            alternate_bases=payload.alternate_bases,
            variant_type=payload.variant_type,
            variant_min_length=payload.variant_min_length,
            variant_max_length=payload.variant_max_length,
            requested_granularity=payload.requested_granularity,
            include_details=payload.include_details,
            include_samples=payload.include_samples,
            dataset_id=ds, vcf_location=f"v{ds[1:]}.vcf.gz", chrom_label=CHROM,
        )
        for ds, rs in recs.items()
    }


def _assert_as_the_oracle(got: list, want: dict, what) -> None:
    assert sorted(r.dataset_id for r in got) == sorted(want), what
    for g in got:
        w = want[g.dataset_id]
        assert (g.exists, g.call_count, g.all_alleles_count) == (
            w.exists, w.call_count, w.all_alleles_count), (what, g.dataset_id)
        assert sorted(g.variants) == sorted(w.variants), (what, g.dataset_id)
    # ... and in aggregate, what the envelope is built from
    assert any(g.exists for g in got) == any(w.exists for w in want.values()), what
    assert sum(g.call_count for g in got) == sum(w.call_count for w in want.values()), what


@pytest.mark.parametrize("granularity", ["boolean", "count"])
@pytest.mark.parametrize("shape", ["point", "range", "n_alternate", "miss"])
def test_every_dataset_answers_as_the_oracle_through_one_mesh_launch(served, shape, granularity):
    eng, recs = served
    payload = _payload(requested_granularity=granularity, **_shape(recs, shape))
    searches, launches = eng.mesh_searches, flight_recorder.launches_by_family().get("mesh", 0)
    got = eng.search(payload)
    assert eng.mesh_searches == searches + 1
    assert flight_recorder.launches_by_family().get("mesh", 0) == launches + 1
    want = _want(recs, payload)
    _assert_as_the_oracle(got, want, (shape, granularity))
    assert any(w.exists for w in want.values()) == (shape != "miss")


def test_the_stack_is_padded_both_ways(served):
    eng, _recs = served
    mesh, stacked, arrays, index_of, _shard_of, _planes_of = eng._mesh_ready()
    assert int(mesh.devices.size) == 8
    assert stacked.n_datasets == N_DATASETS and stacked.n_datasets_padded == 16
    assert len({s.n_rows for s in stacked.shards}) == N_DATASETS
    # resident in lane rows: a dataset's column is [n / 128, 128]
    assert arrays["pos"].shape == (16, stacked.n_padded // 128, 128)
    assert arrays["alt_prefix"].shape == (16, stacked.n_padded // 128, 128, 4)
    assert arrays["chrom_offsets"].shape == (16, 27)
    assert sorted(index_of.values()) == list(range(N_DATASETS))


def test_the_psum_aggregates_are_the_sum_of_the_datasets(served):
    from sbeacon_tpu.parallel.mesh import sharded_query

    eng, recs = served
    mesh, stacked, arrays, *_ = eng._mesh_ready()
    shape = _shape(recs, "range", k=2)
    spec = QuerySpec(CHROM, shape["start_min"], shape["start_max"], 0, 10**9,
                     reference_bases="N", alternate_bases="N")
    cfg = eng.config.engine
    per_ds, agg = sharded_query(
        arrays, [spec], mesh=mesh, n_iters=stacked.n_iters,
        window_cap=cfg.window_cap, record_cap=cfg.record_cap,
        n_datasets=stacked.n_datasets,
    )
    assert per_ds["rows"].shape[:2] == (16, 1)
    assert not per_ds["overflow"].any()
    for leaf in ("call_count", "all_alleles_count", "n_variants"):
        assert int(agg[leaf][0]) == int(per_ds[leaf][:, 0].sum()), leaf
    assert int(agg["n_datasets_hit"][0]) == int(per_ds["exists"][:, 0].sum()) > 0
    assert bool(agg["exists"][0])
    # the six padding datasets answer nothing
    assert not per_ds["exists"][N_DATASETS:, 0].any()
    # ... and the device's own sum is the reference's, dataset by dataset
    want = _want(recs, _payload(start_min=shape["start_min"], start_max=shape["start_max"]))
    assert int(agg["call_count"][0]) == sum(w.call_count for w in want.values())
    assert int(agg["n_datasets_hit"][0]) == sum(1 for w in want.values() if w.exists)


def test_a_request_ticks_the_launch_and_its_stages(served):
    eng, recs = served
    payload = _payload(**_shape(recs, "range", k=5))
    stages = ("kernel.encode", "kernel.dispatch", "kernel.readback", "kernel.unpack")
    before = {s: tracer.stage_counts(s) for s in stages}
    fanout0 = tracer.stage_counts("engine.fanout")[0]
    mat0 = tracer.stage_counts("engine.materialize")
    launches0 = flight_recorder.launches_by_family().get("mesh", 0)
    pairs0, bytes0 = flight_recorder.evaluated_pairs, flight_recorder.fetched_bytes
    targets0, compiles0 = eng.fanout_targets, flight_recorder.mid_request_compiles()
    eng.search(payload)
    for s in stages:
        count, _sum_ms, req_ms = tracer.stage_counts(s)
        assert count == before[s][0] + 1, s
        assert req_ms > before[s][2], (s, "on the request's thread: in the chain")
    # a boolean's or a count's responses are built on the request's own
    # thread, one engine.materialize a dataset, no pool task, no parking
    count, _sum_ms, req_ms = tracer.stage_counts("engine.materialize")
    assert count == mat0[0] + N_DATASETS and req_ms > mat0[2]
    assert tracer.stage_counts("engine.fanout")[0] == fanout0
    assert eng.fanout_targets == targets0
    assert flight_recorder.launches_by_family().get("mesh", 0) == launches0 + 1
    # sixteen dataset slots (ten real), one query: the padded pairs
    assert flight_recorder.evaluated_pairs == pairs0 + 16
    rec = [r for r in flight_recorder.snapshot()["ring"]["entries"] if r["family"] == "mesh"][-1]
    assert (rec["specs"], rec["padded"]) == (N_DATASETS, 16)
    # both device_gets: the [16, 1, record_cap] rows leaf and the rest
    fetched = flight_recorder.fetched_bytes - bytes0
    assert fetched >= 16 * eng.config.engine.record_cap * 4
    assert rec["fetchBytes"] == fetched
    # the program was compiled by warmup(), inside device_warmup_phase
    assert flight_recorder.mid_request_compiles() == compiles0


def test_plane_reading_materialisations_ride_the_pool():
    """With the planes kept on the host by choice the stack still matches
    the rows, and the responses, which read the host's planes, are built
    on the scatter pool under the fan-out's stages and counter."""
    eng, recs = _engine(device_planes=False)
    off, _ = _engine(device_planes=False, use_mesh=False)
    try:
        payload = _payload(
            requested_granularity="record", selected_samples_only=True,
            include_samples=True,
            sample_names={f"d{d}": ["S0", "S2"] for d in range(N_DATASETS)},
            **_shape(recs, "range", k=3),
        )
        fanout0 = tracer.stage_counts("engine.fanout")[0]
        pool0 = tracer.stage_counts("engine.pool_wait")[0]
        searches0, targets0 = eng.mesh_searches, eng.fanout_targets
        got, want = eng.search(payload), off.search(payload)
        assert eng.mesh_searches == searches0 + 1
        assert tracer.stage_counts("engine.fanout")[0] >= fanout0 + 1
        assert tracer.stage_counts("engine.pool_wait")[0] >= pool0 + N_DATASETS
        assert eng.fanout_targets == targets0 + N_DATASETS
        assert any(r.exists for r in got)
        assert [(r.dataset_id, r.exists, r.call_count, r.all_alleles_count,
                 sorted(r.variants), r.sample_indices) for r in got] == [
            (r.dataset_id, r.exists, r.call_count, r.all_alleles_count,
             sorted(r.variants), r.sample_indices) for r in want]
    finally:
        eng.close()
        off.close()


def test_four_threads_launch_the_collective_program_at_once(served):
    eng, recs = served
    jobs = []
    for k in range(50):
        shape = ("point", "range", "n_alternate", "miss")[k % 4]
        payload = _payload(
            requested_granularity=("boolean", "count")[(k // 4) % 2], **_shape(recs, shape, k))
        jobs.append((payload, _want(recs, payload)))
    searches0 = eng.mesh_searches
    launches0 = flight_recorder.launches_by_family().get("mesh", 0)
    errors: list = []
    start = threading.Barrier(4)

    def client(i: int) -> None:
        try:
            start.wait(30)
            for payload, want in jobs[i:] + jobs[:i]:
                _assert_as_the_oracle(eng.search(payload), want, payload)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(240)
    assert not any(t.is_alive() for t in threads), "a client sits in a launch"
    assert not errors, errors[:3]
    assert eng.mesh_searches == searches0 + 200
    assert flight_recorder.launches_by_family().get("mesh", 0) == launches0 + 200


@pytest.mark.parametrize("reason", ["uncovered", "warming"])
def test_a_base_target_off_the_stack_is_counted_and_answered(reason):
    eng, recs = _engine()
    try:
        state = eng._mesh_ready()
        assert state is not None and not eng.mesh_skips
        if reason == "uncovered":
            # the stack stands and lacks one base shard the request targets
            shard_of = dict(state[4])
            del shard_of[("d4", "v4.vcf.gz")]
            eng._mesh_state = state[:4] + (shard_of,) + state[5:]
        else:
            # the state reads None while a rebuild's programs compile
            eng._mesh_state, eng._mesh_why = None, "warming"
        payload = _payload(**_shape(recs, "range"))
        searches0, fallbacks0 = eng.mesh_searches, sum(flight_recorder.fallbacks_by_site().values())
        got = eng.search(payload)
        _assert_as_the_oracle(got, _want(recs, payload), reason)
        assert eng.mesh_skips == {reason: 1}
        # nine of ten still rode the stack; with no stack, none did
        assert eng.mesh_searches - searches0 == (1 if reason == "uncovered" else 0)
        # a skip is not a counted fall-back (engine._note_mesh_skip says why)
        assert sum(flight_recorder.fallbacks_by_site().values()) == fallbacks0
        eng._mesh_state = state
        eng.search(_payload(**_shape(recs, "range", k=1)))
        assert eng.mesh_skips == {reason: 1}, "a covered request is no skip"
    finally:
        eng.close()


def test_no_skip_where_the_mesh_is_not_meant(served):
    eng, recs = served
    off, _ = _engine(use_mesh=False)
    try:
        payload = _payload(**_shape(recs, "range"))
        _assert_as_the_oracle(off.search(payload), _want(recs, payload), "use_mesh off")
        assert off.mesh_skips == {} and off.mesh_searches == 0
    finally:
        off.close()
    # one dataset: the batcher's path, never the stack's, and no skip
    skips = dict(eng.mesh_skips)
    one = eng.search(_payload(dataset_ids=["d2"], **_shape(recs, "n_alternate")))
    assert [r.dataset_id for r in one] == ["d2"] and eng.mesh_skips == skips


def test_the_skip_counter_is_served_with_its_reasons(served):
    from sbeacon_tpu.telemetry import MetricsRegistry

    eng, _recs = served
    registry = MetricsRegistry()
    eng.register_metrics(registry)
    eng.mesh_skips["uncovered"] = eng.mesh_skips.get("uncovered", 0)
    doc = registry.render_json()
    assert doc["engine"]["mesh_skips"] == dict(eng.mesh_skips)
    assert doc["engine"]["mesh_searches"] == eng.mesh_searches
