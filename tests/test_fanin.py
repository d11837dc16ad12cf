"""The launch's own counts decide what is materialised (ISSUE 45).

A multi-dataset boolean or count over the fused stack (one chip) and
over the mesh stack (the tests' eight virtual devices) answers, dataset
by dataset and field by field, what ``materialize_response`` over
``host_match_rows`` answers, whatever the launch said of a dataset:
nothing matched (answered from the count, ``engine.materialized
{skipped}``), rows in hand (on the request's own thread, ``inline``), a
window overflow, more matches than ``record_cap`` or a ref the device
cannot compare exactly (the uncapped host matcher). A request whose
every unit is ready submits nothing to the scatter pool; plane units on
several chips still ride it.
"""

import dataclasses
import random
import threading

import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import (
    VariantEngine,
    host_match_rows,
    materialize_response,
)
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import RequestContext, request_context
from sbeacon_tpu.testing import random_records, synthetic_shard
from sbeacon_tpu.utils.trace import tracer

SAMPLES = ["S0", "S1", "S2", "S3"]
N_DATASETS = 10
CHROM = "7"
WINDOW_CAP, RECORD_CAP = 128, 8


def _build(use_mesh: bool):
    """Ten datasets of unlike sizes (150, 187, ... records from position
    1000 on, so the longer ones reach where the shorter have nothing),
    planes on the host by choice (a selected-samples request then takes
    the stacks too), caps small enough for a range to pass them."""
    eng = VariantEngine(
        BeaconConfig(
            engine=EngineConfig(
                response_cache=False, device_planes=False, use_mesh=use_mesh,
                window_cap=WINDOW_CAP, record_cap=RECORD_CAP,
            )
        )
    )
    shards = {}
    for d in range(N_DATASETS):
        shard = build_index(
            random_records(
                random.Random(4500 + d), chrom=CHROM, n=150 + 37 * d,
                n_samples=len(SAMPLES),
            ),
            dataset_id=f"d{d}", vcf_location=f"v{d}.vcf.gz",
            sample_names=SAMPLES,
        )
        eng.add_index(shard)
        shards[(f"d{d}", f"v{d}.vcf.gz")] = shard
    eng.warmup()
    return eng, shards


@pytest.fixture(scope="module")
def stacks():
    built = {"fused": _build(False), "mesh": _build(True)}
    assert built["fused"][0]._fused_ready() is not None
    assert built["mesh"][0]._mesh_ready() is not None
    yield built
    for eng, _shards in built.values():
        eng.close()


def _rows_in(shard, lo, hi) -> int:
    pos = shard.cols["pos"]
    return int(((pos >= lo) & (pos <= hi)).sum())


def _case(shards: dict, case: str) -> dict:
    """The query of a case, with the case's premise held to the shards."""
    last = shards[("d9", "v9.vcf.gz")]
    tops = sorted(int(s.cols["pos"].max()) for s in shards.values())
    if case == "all_miss":
        return dict(start_min=tops[-1] + 1000, start_max=tops[-1] + 2000)
    if case == "one_hit":
        # a called single-base row of the longest dataset, past the end
        # of every other
        c = last.cols
        row = next(
            r for r in range(last.n_rows - 1, -1, -1)
            if c["pos"][r] > tops[-2] and c["ac"][r] > 0
            and len(last.row_alt(r)) == 1 and last.row_alt(r) in "ACGT"
        )
        p = int(c["pos"][row])
        return dict(start_min=p, start_max=p, alternate_bases=last.row_alt(row))
    if case == "range_most":
        return dict(start_min=1500, start_max=1700)
    if case == "window_overflow":
        q = dict(start_min=2500, start_max=tops[-1])
        met = [_rows_in(s, 2500, tops[-1]) for s in shards.values()]
        assert min(met) <= WINDOW_CAP < max(met), met
        return q
    if case == "over_record_cap":
        q = dict(start_min=1000, start_max=1800)
        assert all(_rows_in(s, 1000, 1800) <= WINDOW_CAP for s in shards.values())
        return q
    if case == "ref_n":
        # a selected-samples request whose ref carries N: regex
        # semantics, the host's alone
        return dict(
            start_min=1500, start_max=1700, selected_samples_only=True,
            sample_names={ds: ["S1", "S3"] for ds, _vcf in shards},
        )
    raise ValueError(case)


def _payload(**kw) -> VariantQueryPayload:
    base = dict(
        dataset_ids=[], reference_name=CHROM, end_min=0, end_max=10**9,
        reference_bases="N", alternate_bases="N",
    )
    base.update(kw)
    return VariantQueryPayload(**base)


def _spec(payload) -> QuerySpec:
    return QuerySpec(
        CHROM, payload.start_min, payload.start_max, payload.end_min,
        payload.end_max, payload.reference_bases, payload.alternate_bases,
    )


def _host_rows(shard, payload) -> np.ndarray:
    return host_match_rows(
        shard, _spec(payload), ref_wildcard=payload.selected_samples_only
    )


def _reference(shards: dict, payload) -> list:
    """Per dataset, in the order of the keys: ``materialize_response``
    over the uncapped host matcher's rows."""
    out = []
    for (ds, vcf), shard in sorted(shards.items()):
        rows = _host_rows(shard, payload)
        selected = (
            [SAMPLES.index(s) for s in payload.sample_names[ds]]
            if payload.selected_samples_only else None
        )
        out.append(materialize_response(
            shard, rows, payload, chrom_label=CHROM, dataset_id=ds,
            vcf_location=vcf, selected_idx=selected,
        ))
    return out


CASES = ("all_miss", "one_hit", "range_most", "window_overflow",
         "over_record_cap", "ref_n")


@pytest.mark.parametrize("include", ["NONE", "HIT", "ALL"])
@pytest.mark.parametrize("granularity", ["boolean", "count"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("stack", ["fused", "mesh"])
def test_a_request_answers_bit_for_bit_what_the_host_matcher_answers(
    stacks, stack, case, granularity, include
):
    eng, shards = stacks[stack]
    payload = _payload(
        requested_granularity=granularity, include_datasets=include,
        **_case(shards, case),
    )
    want = _reference(shards, payload)
    launches = (eng.fused_searches, eng.mesh_searches)
    before = dict(eng.materialized)
    ctx = RequestContext(route="g_variants")
    with request_context(ctx):
        got = eng.search(payload)
    # in the targets' order, each its own dataset and file
    assert [(r.dataset_id, r.vcf_location) for r in got] == sorted(shards)
    assert [dataclasses.asdict(r) for r in got] == [
        dataclasses.asdict(r) for r in want
    ]
    # one launch of the stack under test answered it, but for a ref
    # the fused stack's device compare is not exact for
    fused_launch = stack == "fused" and case != "ref_n"
    assert (eng.fused_searches, eng.mesh_searches) == (
        launches[0] + fused_launch, launches[1] + (stack == "mesh"),
    )
    # every target is counted once, by how its response came to be
    added = {how: eng.materialized[how] - n for how, n in before.items()}
    assert sum(added.values()) == N_DATASETS, added
    n_rows = [len(_host_rows(s, payload)) for s in shards.values()]
    hits = sum(1 for r in want if r.exists)
    if fused_launch:
        # the plan's note says what the rule decided, and why a target
        # was not ready: its launch overflowed and the host matched it
        note = next(e for e in ctx.plan if e["stage"] == "split")["detail"]
        waiting = note["overflow_host"]
        assert (note["skipped"], note["ready"]) == (
            added["skipped"], N_DATASETS - added["skipped"] - waiting,
        )
        # two or more that still have a host match to pay ride the pool
        assert added["pooled"] == (waiting if waiting > 1 else 0)
        assert note["overflow_host"] == sum(
            1 for s in shards.values()
            if _rows_in(s, payload.start_min, payload.start_max) > WINDOW_CAP
            or len(_host_rows(s, payload)) > RECORD_CAP
        )
        assert bool(note["overflow_host"]) == (
            case in ("window_overflow", "over_record_cap")
        )
    if case == "all_miss":
        assert added == {"skipped": N_DATASETS, "inline": 0, "pooled": 0}
    elif case == "one_hit":
        assert hits == 1 and n_rows.count(0) == N_DATASETS - 1
        assert added == {"skipped": N_DATASETS - 1, "inline": 1, "pooled": 0}
    elif case == "range_most":
        assert sum(1 for n in n_rows if 0 < n <= RECORD_CAP) > N_DATASETS // 2
        assert added["pooled"] == 0 and added["inline"] >= hits
    elif case == "over_record_cap":
        assert max(n_rows) > RECORD_CAP
    elif case == "ref_n":
        # nothing is skipped on a count the device could not take exactly
        assert not eng._device_ref_ok(payload, _spec(payload))
        assert added["skipped"] == 0


def test_a_request_whose_every_unit_is_ready_never_meets_the_pool(
    stacks, monkeypatch
):
    """Thirty-two pool tasks a request were ``mds.fanout``'s: a count
    over the fused stack builds every response on the request's own
    thread, under ``engine.materialize``, and parks nowhere."""
    eng, shards = stacks["fused"]
    made, submitted = [], []
    real = engine_mod.materialize_response

    def recording(*args, **kwargs):
        made.append(threading.get_ident())
        return real(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "materialize_response", recording)
    monkeypatch.setattr(
        eng._scatter, "submit",
        lambda *a, **kw: submitted.append(a) or pytest.fail("a pool task"),
    )
    for case in ("range_most", "one_hit", "all_miss"):
        payload = _payload(
            requested_granularity="count", include_datasets="ALL",
            **_case(shards, case),
        )
        del made[:]
        fanout0 = tracer.stage_counts("engine.fanout")[0]
        pool0 = tracer.stage_counts("engine.pool_wait")[0]
        mat0 = tracer.stage_counts("engine.materialize")[0]
        targets0, before = eng.fanout_targets, dict(eng.materialized)
        got = eng.search(payload)
        assert [dataclasses.asdict(r) for r in got] == [
            dataclasses.asdict(r) for r in _reference(shards, payload)
        ]
        inline = eng.materialized["inline"] - before["inline"]
        skipped = eng.materialized["skipped"] - before["skipped"]
        assert inline + skipped == N_DATASETS and not submitted
        assert made == [threading.get_ident()] * inline
        assert tracer.stage_counts("engine.fanout")[0] == fanout0
        assert tracer.stage_counts("engine.pool_wait")[0] == pool0
        assert eng.fanout_targets == targets0
        # a scope a materialised dataset, ONE around the misses
        assert tracer.stage_counts("engine.materialize")[0] == (
            mat0 + inline + (1 if skipped else 0)
        )


def test_plane_units_on_several_chips_still_ride_the_pool(monkeypatch):
    """``kg4.samples``' shape: a filtered record request over four
    cohorts whose planes lie on four owner chips is four units, each
    with a device round trip to pay: a pool task each, as before."""
    monkeypatch.setattr(
        engine_mod, "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    eng = VariantEngine(
        BeaconConfig(
            engine=EngineConfig(
                response_cache=False, microbatch=False, window_cap=512
            )
        )
    )
    try:
        cohorts = []
        for d in range(4):
            shard = synthetic_shard(
                900, n_samples=59, seed=70 + d, dataset_id=f"k{d}",
                chroms=[CHROM], with_gt_planes=True, plane_density=0.1,
            )
            shard.meta["vcf_location"] = f"k{d}.vcf"
            eng.add_index(shard)
            cohorts.append(shard)
        assert eng.warmup() > 0 and eng.warmup_failed_phases == 0
        owners = {row["chip"] for row in eng.placement_table()}
        assert len(owners) == 4, owners
        pos = cohorts[0].cols["pos"]
        payload = VariantQueryPayload(
            dataset_ids=[f"k{d}" for d in range(4)], reference_name=CHROM,
            start_min=int(pos[100]), start_max=int(pos[160]),
            end_min=int(pos[100]), end_max=1 << 30, alternate_bases="N",
            requested_granularity="record", include_datasets="HIT",
            include_samples=True, selected_samples_only=True,
            sample_names={
                f"k{d}": s.meta["sample_names"][3:11]
                for d, s in enumerate(cohorts)
            },
        )
        fanout0 = tracer.stage_counts("engine.fanout")[0]
        pool0 = tracer.stage_counts("engine.pool_wait")[0]
        before = dict(eng.materialized)
        got = eng.search(payload)
        assert tracer.stage_counts("engine.fanout")[0] == fanout0 + 1
        assert tracer.stage_counts("engine.pool_wait")[0] == pool0 + 4
        assert {h: eng.materialized[h] - n for h, n in before.items()} == {
            "skipped": 0, "inline": 0, "pooled": 4,
        }
        assert eng.fanout_targets == eng.materialized["pooled"]
        for r, shard in zip(got, cohorts):
            names = shard.meta["sample_names"]
            want = materialize_response(
                shard, _host_rows(shard, payload), payload, chrom_label=CHROM, dataset_id=r.dataset_id,
                vcf_location=shard.meta["vcf_location"],
                selected_idx=[names.index(s) for s in payload.sample_names[r.dataset_id]],
            )
            assert dataclasses.asdict(r) == dataclasses.asdict(want)
        assert any(r.exists for r in got)
    finally:
        eng.close()


def test_the_counter_is_served_with_its_three_ways(stacks):
    from sbeacon_tpu.telemetry import MetricsRegistry

    eng, _shards = stacks["mesh"]
    registry = MetricsRegistry()
    eng.register_metrics(registry)
    doc = registry.render_json()
    assert doc["engine"]["materialized"] == dict(eng.materialized)
    assert set(doc["engine"]["materialized"]) == {"skipped", "inline", "pooled"}
    assert doc["engine"]["fanout_targets"] == eng.materialized["pooled"]
