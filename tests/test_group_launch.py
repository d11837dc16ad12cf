"""One plane launch an owner chip (ISSUE 44): the fused match+planes
program takes a GROUP of datasets, a slot each over that dataset's own
resident buffers, one packed operand up and one packed result back. A
group answers field by field what its members' own launches answer;
through the engine a request over sixteen cohorts of one chip is one
``plane`` launch on the request's own thread."""

import random

import jax
import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.telemetry as tel
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import (
    VariantEngine,
    host_match_rows,
    materialize_response_loop,
)
from sbeacon_tpu.index.columnar import FLAG
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.plane_kernel import (
    PlaneDeviceIndex,
    pack_factor,
    sample_mask_words,
)
from sbeacon_tpu.ops.scatter_kernel import (
    SELECTED_SLOTS,
    ScatterDeviceIndex,
    run_selected_group,
    run_selected_scattered,
)
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import synthetic_shard
from sbeacon_tpu.utils.trace import tracer

FIELDS = (
    "exists", "call_count", "n_variants", "all_alleles_count", "n_matched",
    "overflow", "rows", "pc_call", "pc_tok", "or_words",
)


@pytest.fixture(autouse=True)
def _release_the_programs_a_test_compiled():
    """A group's program is compiled a (group, tier, exact, counts) and
    the one-slot launches it is held to a member's shape: hundreds of
    CPU executables a test, each dozens of memory mappings of the
    worker's process (49,949 after this file's kernel tests, of the
    65,530 a process may hold: a worker that ran other modules before
    it died in XLA's compiler). Dropped after every test."""
    yield
    jax.clear_caches()


def _shard(i, n_words, with_counts, *, rows=None, multiallelic=None):
    """Cohort ``i`` of a chip: one of three row counts and densities (so
    one position window is single-tile here and crosses a tile there)
    and one of two longest records (so the members' ``seg_k`` differ);
    its own rows and carriers whatever its shape."""
    shard = synthetic_shard(
        rows or 700 + 173 * (i % 3),
        n_samples=32 * n_words - 5,
        seed=40 + i,
        dataset_id=f"g{i:02d}",
        chroms=["7"],
        p_multiallelic=(0.0 if i % 2 else 0.3)
        if multiallelic is None else multiallelic,
        with_gt_planes=True,
        plane_density=0.06,
    )
    shard.meta["vcf_location"] = f"g{i:02d}.vcf"
    if with_counts:
        # genotype-derived rows: their counts come from the planes
        shard.cols["flags"][::3] &= ~np.int32(FLAG.AC_INFO | FLAG.AN_INFO)
    return shard


def _members(n, n_words, with_counts):
    shards = [_shard(i, n_words, with_counts) for i in range(n)]
    return shards, [
        (ScatterDeviceIndex(s), PlaneDeviceIndex(s)) for s in shards
    ]


def _queries(shards, seed, n=10):
    """Windows by POSITION around the first member's tile edges: each
    member finds its own rows under them, in its own tier."""
    rng = random.Random(seed)
    pos = shards[0].cols["pos"]
    out = []
    for _ in range(n):
        i = rng.choice([rng.randrange(len(pos) - 60), 120, 250, 380])
        j = i + rng.choice([0, 3, 12, 40])
        out.append(
            QuerySpec(
                "7", int(pos[i]), int(pos[j]), 1, 1 << 30,
                alternate_bases=rng.choice(["N", "N", "T", None]),
                variant_type=rng.choice([None, None, "DEL"]),
            )
        )
    return out


def _masks(rng, n, n_words):
    """An unlike mask a slot (one of them every sample)."""
    n_samples = 32 * n_words - 5
    return [
        np.full(n_words, 0xFFFFFFFF, np.uint32)
        if d == 1
        else sample_mask_words(
            rng.sample(range(n_samples), 5 + d % 7), n_words
        )
        for d in range(n)
    ]


@pytest.mark.parametrize("with_counts", [False, True])
@pytest.mark.parametrize("n_words", [32, 79])  # k = 4 packed, k = 1 wide
@pytest.mark.parametrize("d_asked,d_slots", [(1, 1), (2, 2), (3, 5), (16, 16)])
def test_a_group_answers_what_its_members_own_launches_answer(
    d_asked, d_slots, n_words, with_counts
):
    """D datasets in one launch (``d_asked`` of the block's ``d_slots``
    asked, the rest padding slots under a query that matches nothing)
    against D launches of one: every field of every asked slot, under
    unlike masks, unlike tiers and unlike ``seg_k``."""
    assert pack_factor(n_words) == (4 if n_words == 32 else 1)
    shards, members = _members(d_slots, n_words, with_counts)
    assert len({m[0].seg_k for m in members}) > 1 or d_slots == 1
    rng = random.Random(7 * d_slots + n_words)
    asked = sorted(rng.sample(range(d_slots), d_asked))
    tiers = set()
    # a launch a query and slot on the other side: fewer for the wide group
    for q in _queries(shards, d_slots + n_words, n=10 if d_slots < 16 else 5):
        masks = [None] * d_slots
        for d, m in zip(asked, _masks(rng, d_asked, n_words)):
            masks[d] = m
        got = run_selected_group(
            members, q, masks, window_cap=512, record_cap=64,
            with_counts=with_counts,
        )
        for d in range(d_slots):
            if masks[d] is None:
                assert not got.exists[d] and not got.overflow[d]
                assert (got.rows[d] == -1).all() and not got.or_words[d].any()
                continue
            own = run_selected_scattered(
                *members[d], [q], masks[d][None, :],
                window_cap=512, record_cap=64, with_counts=with_counts,
            )
            tiers.add(int(own.n_matched[0]) > 0)
            for f in FIELDS:
                np.testing.assert_array_equal(
                    getattr(got, f)[d], getattr(own, f)[0], err_msg=f
                )
    assert True in tiers  # the windows did match rows


def test_members_of_one_launch_sit_in_unlike_tiers():
    """The launch runs at its WIDEST member's tier; a member whose own
    window lies in one tile reads the same rows under the larger cap."""
    from sbeacon_tpu.ops.query_pack import window_bounds
    from sbeacon_tpu.ops.kernel import encode_queries

    shards, members = _members(4, 32, False)
    pos = shards[0].cols["pos"]
    q = QuerySpec("7", int(pos[120]), int(pos[140]), 1, 1 << 30,
                  alternate_bases="N")
    enc = encode_queries([q])
    crossing = []
    for sindex, _p in members:
        (lo,), (hi,) = window_bounds(sindex, enc)
        crossing.append((hi - 1) // sindex.tile > lo // sindex.tile)
    assert True in crossing and False in crossing
    masks = _masks(random.Random(1), 4, 32)
    got = run_selected_group(members, q, masks, window_cap=512, record_cap=64)
    for d in range(4):
        own = run_selected_scattered(
            *members[d], [q], masks[d][None, :], window_cap=512, record_cap=64
        )
        for f in FIELDS:
            np.testing.assert_array_equal(
                getattr(got, f)[d], getattr(own, f)[0], err_msg=f
            )


def test_an_overflowing_member_overflows_alone():
    """A member that matches more rows than the launch returns reports
    ``overflow`` (the caller walks its split path); its neighbours keep
    the group's rows."""
    dense = _shard(0, 32, False, rows=6000, multiallelic=0.0)
    sparse = [_shard(i, 32, False, rows=300) for i in (1, 2)]
    shards = [sparse[0], dense, sparse[1]]
    members = [(ScatterDeviceIndex(s), PlaneDeviceIndex(s)) for s in shards]
    pos = dense.cols["pos"]
    q = QuerySpec("7", int(pos[100]), int(pos[400]), 1, 1 << 30,
                  alternate_bases="N")
    masks = _masks(random.Random(2), 3, 32)
    got = run_selected_group(members, q, masks, window_cap=512, record_cap=64)
    assert list(got.overflow) == [False, True, False]
    for d in (0, 2):
        rows = host_match_rows(shards[d], q)
        assert len(rows) and list(got.rows[d][got.rows[d] >= 0]) == list(rows)
        own = run_selected_scattered(
            *members[d], [q], masks[d][None, :], window_cap=512, record_cap=64
        )
        assert not own.overflow[0]
        np.testing.assert_array_equal(got.or_words[d], own.or_words[0])
        np.testing.assert_array_equal(
            got.pc_call[d][: len(rows)], own.pc_call[0][: len(rows)]
        )


# -- through the engine -------------------------------------------------------


@pytest.fixture
def one_chip(monkeypatch):
    """The chip's index family on the CPU with every key owned by ONE
    device (tiles and planes both where the engine places them), under
    a fresh flight recorder."""
    monkeypatch.setattr(jax, "local_devices", lambda: jax.devices()[:1])
    monkeypatch.setattr(
        engine_mod,
        "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    monkeypatch.setattr(tel, "flight_recorder", tel.DeviceFlightRecorder())
    return tel.flight_recorder


def _engine():
    return VariantEngine(
        BeaconConfig(
            engine=EngineConfig(
                use_mesh=False, microbatch=False, window_cap=512
            )
        )
    )


def _payload(shards, lo, hi, *, n_selected=7, seed=0):
    rng = random.Random(seed)
    return VariantQueryPayload(
        dataset_ids=[s.meta["dataset_id"] for s in shards],
        reference_name="7", start_min=lo, start_max=hi, end_min=lo,
        end_max=1 << 30, alternate_bases="N",
        requested_granularity="record", include_datasets="HIT",
        include_samples=True, selected_samples_only=True,
        # an unlike selection a dataset: each slot uploads its own mask
        sample_names={
            s.meta["dataset_id"]: rng.sample(
                s.meta["sample_names"], n_selected + i % 3
            )
            for i, s in enumerate(shards)
        },
        no_response_cache=True,
    )


def _reference(shard, payload):
    ds = shard.meta["dataset_id"]
    names = shard.meta["sample_names"]
    rows = host_match_rows(
        shard,
        QuerySpec(
            "7", payload.start_min, payload.start_max, payload.end_min,
            payload.end_max, None, payload.alternate_bases,
        ),
        ref_wildcard=True,
    )
    return materialize_response_loop(
        shard, rows, payload, chrom_label="7", dataset_id=ds,
        vcf_location=shard.meta["vcf_location"],
        selected_idx=[names.index(s) for s in payload.sample_names[ds]],
    )


def _stage_counts(*names):
    return {n: tracer.stage_counts(n)[0] for n in names}


@pytest.mark.parametrize("with_counts", [False, True])
def test_a_request_over_sixteen_cohorts_of_one_chip_is_one_launch(
    one_chip, with_counts
):
    """A filtered record request over all sixteen datasets of a chip:
    ONE ``plane`` launch of sixteen targets, on the request's own
    thread (no ``engine.fanout``, no pool task), every dataset's
    response the CPU oracle's; nothing compiles in a request after
    ``warmup()``, nor after a later ``add_index`` on the same chip,
    which joins the group."""
    # cohorts of two sizes and longest records: the indexes of one
    # shape share their own programs, the group's takes them all
    shards = [
        _shard(i, 32, with_counts, rows=900 + 400 * (i % 2))
        for i in range(16)
    ]
    eng = _engine()
    try:
        for s in shards:
            eng.add_index(s)
        assert eng.warmup() > 0 and eng.warmup_failed_phases == 0
        pos = shards[0].cols["pos"]
        stages = ("engine.fanout", "engine.pool_wait", "kernel.dispatch",
                  "engine.materialize")
        for k, (i, j) in enumerate([(120, 140), (300, 303), (510, 560)]):
            payload = _payload(shards, int(pos[i]), int(pos[j]), seed=k)
            launches = one_chip.launches_by_family().get("plane", 0)
            targets = one_chip.launch_targets_by_family().get("plane", 0)
            before = _stage_counts(*stages)
            fanned = eng.fanout_targets
            got = eng.search(payload)
            after = _stage_counts(*stages)
            assert one_chip.launches_by_family()["plane"] == launches + 1
            assert (
                one_chip.launch_targets_by_family()["plane"] == targets + 16
            )
            assert after["engine.fanout"] == before["engine.fanout"]
            assert after["engine.pool_wait"] == before["engine.pool_wait"]
            assert after["kernel.dispatch"] == before["kernel.dispatch"] + 1
            assert (
                after["engine.materialize"]
                == before["engine.materialize"] + 16
            )
            assert eng.fanout_targets == fanned
            assert got == [_reference(s, payload) for s in shards]
            assert any(r.exists for r in got)
        # three of the sixteen: still one launch (thirteen padding slots)
        some = [shards[2], shards[7], shards[11]]
        payload = _payload(some, int(pos[120]), int(pos[160]), seed=9)
        launches = one_chip.launches_by_family()["plane"]
        targets = one_chip.launch_targets_by_family()["plane"]
        assert eng.search(payload) == [_reference(s, payload) for s in some]
        assert one_chip.launches_by_family()["plane"] == launches + 1
        assert one_chip.launch_targets_by_family()["plane"] == targets + 3
        # one dataset: the same program at one slot
        payload = _payload(shards[4:5], int(pos[120]), int(pos[160]))
        assert eng.search(payload) == [_reference(shards[4], payload)]
        assert one_chip.launches_by_family()["plane"] == launches + 2
        assert one_chip.mid_request_compiles() == 0
        # a seventeenth cohort on a serving engine: past the ceiling of
        # one launch, it opens the chip's second group and is launched
        # alone; an eighteenth makes that a group of two, compiled at
        # its publish
        late = [_shard(i, 32, with_counts, rows=900) for i in (16, 17)]
        for n_groups, s in zip((2, 2), late):
            eng.add_index(s)
            assert len({
                g for g, _slot in eng._plane_groups.values()
            }) == n_groups
        everyone = shards + late
        assert len(everyone) == SELECTED_SLOTS + 2
        payload = _payload(everyone, int(pos[120]), int(pos[140]), seed=3)
        launches = one_chip.launches_by_family()["plane"]
        before = _stage_counts(*stages)
        got = eng.search(payload)
        assert got == [_reference(s, payload) for s in everyone]
        # two groups of one chip: two launches, from the pool
        assert one_chip.launches_by_family()["plane"] == launches + 2
        assert (
            _stage_counts(*stages)["engine.pool_wait"]
            == before["engine.pool_wait"] + 2
        )
        assert one_chip.mid_request_compiles() == 0
        assert one_chip.fallbacks_by_site() == {}
        assert eng.warmup_failed_phases == 0
    finally:
        eng.close()


def test_a_member_that_overflows_walks_its_split_path_alone(one_chip):
    """One cohort of the group matches more rows than a launch returns:
    it is answered by its split path (device rows, ``plane_row_stats``),
    the others by the group's launch, all as the oracle answers."""
    dense = _shard(1, 32, False, rows=40000, multiallelic=0.0)
    shards = [_shard(0, 32, False), dense, _shard(2, 32, False)]
    eng = _engine()
    try:
        for s in shards:
            eng.add_index(s)
        eng.warmup()
        pos = dense.cols["pos"]
        payload = _payload(shards, int(pos[2000]), int(pos[3500]))
        launches = one_chip.launches_by_family()["plane"] if (
            "plane" in one_chip.launches_by_family()
        ) else 0
        got = eng.search(payload)
        assert got == [_reference(s, payload) for s in shards]
        assert len(got[1].variants) > eng.config.engine.record_cap
        # the group's launch, then the overflowing member's own plane
        # reductions (chunks of its matched rows)
        assert one_chip.launches_by_family()["plane"] > launches + 1
        assert one_chip.fallbacks_by_site() == {}
    finally:
        eng.close()


def test_a_failed_group_launch_is_counted_once_and_every_member_answers(
    one_chip,
):
    from sbeacon_tpu.harness import faults

    shards = [_shard(i, 32, False) for i in range(3)]
    eng = _engine()
    try:
        for s in shards:
            eng.add_index(s)
        faults.install({"rules": [
            {"site": "device.bringup", "match": "fused_selected", "count": 1}
        ]})
        pos = shards[0].cols["pos"]
        payload = _payload(shards, int(pos[120]), int(pos[140]))
        assert eng.search(payload) == [_reference(s, payload) for s in shards]
        assert one_chip.fallbacks_by_site() == {"fused_selected": 1}
    finally:
        faults.uninstall()
        eng.close()


def test_a_reingest_frees_its_old_planes_before_the_new_upload(
    one_chip, monkeypatch
):
    """A re-ingest republishes its key plane-less so that the old
    planes' memory is free BEFORE the new upload (at biobank width old
    and new do not fit one chip together). A launch group names every
    member's buffers, so the key's group goes in that same critical
    section: at the upload nothing holds the old planes, the other
    members ride alone and answer, and the publish forms the group
    again, compiled, around the new buffers."""
    import gc
    import weakref

    import sbeacon_tpu.ops.plane_kernel as plane_mod

    shards = [_shard(i, 32, False) for i in range(3)]
    eng = _engine()
    try:
        for s in shards:
            eng.add_index(s)
        eng.warmup()
        key = ("g01", "g01.vcf")
        assert len(eng._plane_groups[key][0]) == 3
        old = weakref.ref(eng._indexes[key][2])
        assert old() is not None
        pos = shards[0].cols["pos"]
        others = [shards[0], shards[2]]
        payload = _payload(others, int(pos[120]), int(pos[140]))
        at_upload = {}

        class Upload(plane_mod.PlaneDeviceIndex):
            def __init__(self, *args, **kw):
                gc.collect()
                at_upload["old planes alive"] = old() is not None
                at_upload["groups"] = dict(eng._plane_groups)
                at_upload["others answer"] = eng.search(payload) == [
                    _reference(s, payload) for s in others
                ]
                at_upload["launches"] = one_chip.launches_by_family()["plane"]
                super().__init__(*args, **kw)

        monkeypatch.setattr(plane_mod, "PlaneDeviceIndex", Upload)
        eng.add_index(_shard(1, 32, False))
        assert at_upload == {
            "old planes alive": False,
            "groups": {},
            "others answer": True,
            "launches": 2,
        }
        group, slot = eng._plane_groups[key]
        assert len(group) == 3 and group[slot][2] is eng._indexes[key][2]
        launches = one_chip.launches_by_family()["plane"]
        payload = _payload(shards, int(pos[120]), int(pos[140]))
        assert eng.search(payload) == [_reference(s, payload) for s in shards]
        assert one_chip.launches_by_family()["plane"] == launches + 1
        assert one_chip.mid_request_compiles() == 0
        assert one_chip.fallbacks_by_site() == {}
    finally:
        eng.close()
