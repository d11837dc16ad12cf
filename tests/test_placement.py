"""Owner-chip placement: every published key has ONE owner among the
local devices; its tiles and its planes are resident there, once, and
every request shape is served from that copy with no counted fall-back.
Run on the conftest's eight virtual CPU devices with the chip's index
family forced (``make_device_index`` picks it only on a TPU).
"""

import dataclasses
import random
import threading

import jax
import numpy as np
import pytest

import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.ops.plane_kernel as plane_mod
from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import (
    VariantEngine,
    host_match_rows,
    materialize_response_loop,
)
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.plane_kernel import resident_shape
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.telemetry import flight_recorder
from sbeacon_tpu.testing import synthetic_shard

N_SAMPLES = 40
N_ROWS = 4000


def _plane_bytes(shard) -> int:
    """One plane resident: 40 samples are 2 words, 64 rows to a lane
    row, int32[ceil(n_rows / 64), 128]."""
    lane_rows, lanes = resident_shape(shard.n_rows, 2)
    assert lanes == 128 and lane_rows == -(-shard.n_rows // 128) * 2
    return lane_rows * lanes * 4


@pytest.fixture
def chips(monkeypatch):
    """``chips(n)``: the process sees ``n`` local devices and builds the
    chip's index family on them."""
    monkeypatch.setattr(
        engine_mod,
        "make_device_index",
        lambda shard, **kw: ScatterDeviceIndex(shard, device=kw.get("device")),
    )
    every = jax.local_devices()

    def limit(n):
        monkeypatch.setattr(jax, "local_devices", lambda *a, **kw: every[:n])
        return every[:n]

    return limit


def _shard(d: int, seed: int = 70):
    return synthetic_shard(
        N_ROWS, n_samples=N_SAMPLES, seed=seed + d, dataset_id=f"pl{d}",
        chroms=["1"], with_gt_planes=True, plane_density=0.2,
    )


def _engine(n_datasets=4, **over):
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(**over)))
    shards = [_shard(d) for d in range(n_datasets)]
    for shard in shards:
        eng.add_index(shard)
    return eng, shards


def _key(shard):
    return (shard.meta["dataset_id"], shard.meta["vcf_location"])


@pytest.mark.parametrize("n_chips", [1, 4])
def test_every_dataset_has_one_owner_and_lies_there(chips, n_chips):
    devices = chips(n_chips)
    eng, shards = _engine(use_mesh=n_chips > 1)
    try:
        triples = {k: (eng._indexes[k][1], p)
                   for k, _s, p in eng.index_snapshot()}
        owners = [d.device for d, _p in triples.values()]
        assert len(set(owners)) == min(4, n_chips)
        assert set(owners) <= set(devices)
        for dindex, planes in triples.values():
            assert planes is not None and planes.device is dindex.device
            assert dindex.tiles.devices() == {dindex.device}
            for a in planes.planes():
                assert a.devices() == {dindex.device}
                assert a.shape == resident_shape(planes.n_rows, 2)
        table = eng.placement_table()
        assert [row["dataset"] for row in table] == [f"pl{d}" for d in range(4)]
        assert {row["chip"] for row in table} == {d.id for d in set(owners)}
        # what the gauge reports per chip is what the arrays hold there
        held = eng.resident_bytes()
        for dev in set(owners):
            assert held[(str(dev.id), "planes")] == sum(
                p.nbytes_hbm() for d, p in triples.values() if d.device is dev
            )
            assert held[(str(dev.id), "tiles")] == sum(
                d.nbytes() for d, _p in triples.values() if d.device is dev
            )
        assert sum(
            v for (_c, kind), v in held.items() if kind == "planes"
        ) == eng.plane_ledger()["residentBytes"]
    finally:
        eng.close()


@pytest.mark.parametrize("n_chips,planed", [(4, 4), (1, 1)])
def test_the_plane_budget_is_a_chip_s(chips, n_chips, planed):
    """A budget of one plane and a half: four planes pass on four chips
    (the old gate, one sum for the process, refused the second), two on
    one chip still do not."""
    chips(n_chips)
    eng, shards = _engine(
        use_mesh=False, plane_hbm_budget_gb=1.5 * _plane_bytes(_shard(0)) / 1e9
    )
    try:
        got = [p for _k, _s, p in eng.index_snapshot() if p is not None]
        assert len(got) == planed
        ledger = eng.plane_ledger()
        assert ledger["residentBytes"] == sum(
            _plane_bytes(s) for s in shards[:planed])
        assert ledger["fullestChipBytes"] == max(
            _plane_bytes(s) for s in shards[:planed])
        assert ledger["reservedBytes"] == 0
    finally:
        eng.close()


def _site(name: str) -> int:
    return flight_recorder.fallbacks_by_site().get(name, 0)


@pytest.mark.parametrize(
    "case", ["one", "second-same-chip", "second-other-chip", "same-key-race"]
)
def test_the_upload_gate_holds_a_chip_to_its_budget(chips, monkeypatch, case):
    """The gate ``add_index`` holds a plane to, with a budget a little
    over one plane's bytes: one plane is admitted and resident and its
    requests count no fall-back; a second on the same chip is declined
    (read from the host, counted), a second on another chip admitted;
    and of two concurrent uploads of one key only one passes, the first
    one's reservation standing in ``plane_ledger()`` while its bytes are
    in flight and gone once they are published."""
    one = _plane_bytes(_shard(0))
    budget = 1.2 * one / 1e9
    chips(2 if case == "second-other-chip" else 1)
    n = 1 if case in ("one", "same-key-race") else 2
    if case != "same-key-race":
        eng, shards = _engine(
            n_datasets=n, use_mesh=False, plane_hbm_budget_gb=budget
        )
        try:
            planed = [
                k for k, _s, p in eng.index_snapshot() if p is not None
            ]
            want = 1 if case == "second-same-chip" else n
            assert planed == [_key(s) for s in shards[:want]]
            assert eng._planes_declined == {_key(s) for s in shards[want:]}
            ledger = eng.plane_ledger()
            assert ledger["residentBytes"] == want * one
            assert ledger["fullestChipBytes"] == one
            assert ledger["reservedBytes"] == ledger["reservedTokens"] == 0
            assert ledger["headroomBytes"] == int(budget * 1e9) - one
            before = _site("host_planes")
            for k, shard in enumerate(shards):
                pay = _payload(
                    shard, [shard.meta["dataset_id"]], selected=True
                )
                got, ref = eng.search(pay), _reference(shards, pay)
                assert [r.sample_indices for r in got] == [
                    r.sample_indices for r in ref]
                assert _site("host_planes") == before + max(0, k + 1 - want)
        finally:
            eng.close()
        return

    # two uploads of ONE key at once: the first is held inside its
    # upload, its reservation standing; the second meets the gate
    real = plane_mod.PlaneDeviceIndex
    entered, go = threading.Event(), threading.Event()
    uploads = []

    class Held(real):
        def __init__(self, shard, **kw):
            uploads.append(shard)
            if len(uploads) == 1:
                entered.set()
                assert go.wait(60)
            super().__init__(shard, **kw)

    monkeypatch.setattr(plane_mod, "PlaneDeviceIndex", Held)
    eng = VariantEngine(BeaconConfig(engine=EngineConfig(
        use_mesh=False, plane_hbm_budget_gb=budget)))
    shard = _shard(0)
    first = threading.Thread(target=eng.add_index, args=(shard,))
    try:
        first.start()
        assert entered.wait(60)
        ledger = eng.plane_ledger()
        assert ledger["reservedBytes"] == one
        assert ledger["reservedTokens"] == 1
        assert ledger["residentBytes"] == 0
        assert ledger["fullestChipBytes"] == one
        eng.add_index(shard)  # the second upload: declined at the gate
        assert len(uploads) == 1
        assert eng._planes_declined == {_key(shard)}
        assert all(p is None for _k, _s, p in eng.index_snapshot())
        go.set()
        first.join(60)
        assert not first.is_alive()
        ledger = eng.plane_ledger()
        assert ledger["reservedBytes"] == ledger["reservedTokens"] == 0
        assert ledger["residentBytes"] == ledger["fullestChipBytes"] == one
        assert len(uploads) == 1
        (_k, _s, planes), = eng.index_snapshot()
        assert planes is not None
    finally:
        go.set()
        first.join(60)
        eng.close()


def test_a_republish_keeps_its_owner(chips):
    chips(4)
    eng, shards = _engine()
    try:
        before = {row["dataset"]: row["chip"] for row in eng.placement_table()}
        assigned = dict(eng._assignments)
        eng.warmup()  # serving: the republish uploads on the caller's thread
        eng.add_index(_shard(2, seed=500))
        eng.add_index(_shard(0, seed=600))
        after = {row["dataset"]: row["chip"] for row in eng.placement_table()}
        assert after == before and eng._assignments == assigned
        for key, _shard_, planes in eng.index_snapshot():
            assert planes.device.id == before[key[0]]
            assert eng._indexes[key][1].device.id == before[key[0]]
        # a dropped dataset gives its chip back to the next new key
        freed = before["pl1"]
        assert eng.drop_dataset("pl1") == 1
        late = synthetic_shard(
            N_ROWS, n_samples=N_SAMPLES, seed=9, dataset_id="pl9",
            chroms=["1"], with_gt_planes=True, plane_density=0.2,
        )
        eng.add_index(late)
        assert eng._indexes[_key(late)][1].device.id == freed
    finally:
        eng.close()


def _payload(shard, datasets, *, selected, granularity="record", width=3000):
    pos = int(shard.cols["pos"][N_ROWS // 2])
    names = shard.meta["sample_names"]
    return VariantQueryPayload(
        dataset_ids=datasets,
        reference_name="1",
        start_min=max(1, pos - width),
        start_max=pos + width,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        include_datasets="HIT",
        requested_granularity=granularity,
        include_samples=selected,
        selected_samples_only=selected,
        sample_names=(
            {f"pl{d}": names[d::7] for d in range(4)} if selected else {}
        ),
    )


def _reference(shards, pay):
    """The per-record loop over the host matcher's rows: the executable
    spec ``materialize_response`` is fuzz-tested against."""
    spec = QuerySpec(
        pay.reference_name, pay.start_min, pay.start_max, pay.end_min,
        pay.end_max, alternate_bases=pay.alternate_bases,
    )
    out = []
    for shard in shards:
        ds = shard.meta["dataset_id"]
        if pay.dataset_ids and ds not in pay.dataset_ids:
            continue
        sel = None
        if pay.selected_samples_only:
            names = shard.meta["sample_names"]
            sel = [names.index(n) for n in pay.sample_names[ds]]
        rows = host_match_rows(
            shard, spec, ref_wildcard=pay.selected_samples_only
        )
        out.append(materialize_response_loop(
            shard, rows, pay, chrom_label="1", dataset_id=ds,
            selected_idx=sel,
        ))
    return out


@pytest.mark.parametrize("microbatch", [False, True])
def test_both_request_shapes_are_served_from_the_owners(chips, microbatch):
    """``/g_variants`` over all four datasets and over one, boolean,
    count, record and selected-samples alike: the answers of the host
    reference, no counted fall-back, nothing compiled after warm-up,
    and every owner chip launched on."""
    chips(4)
    eng, shards = _engine(microbatch=microbatch)
    try:
        eng.warmup()
        assert eng.warmup_failed_phases == 0
        compiles = flight_recorder.mid_request_compiles()
        fallbacks = sum(flight_recorder.fallbacks_by_site().values())
        by_chip = flight_recorder.launches_by_chip()
        mesh_before = eng.mesh_searches
        asked = 0
        for datasets in ([], ["pl2"]):
            for selected in (False, True):
                for granularity in ("boolean", "count", "record"):
                    pay = _payload(
                        shards[asked % 4], datasets, selected=selected,
                        granularity=granularity, width=1_000_000 + 50_000 * asked,
                    )
                    asked += 1
                    got, want = eng.search(pay), _reference(shards, pay)
                    assert len(got) == len(want) == (1 if datasets else 4)
                    for a, b in zip(got, want):
                        assert a.dataset_id == b.dataset_id
                        assert (a.exists, a.call_count, a.all_alleles_count) == (
                            b.exists, b.call_count, b.all_alleles_count)
                        assert a.variants == b.variants
                        assert a.sample_indices == b.sample_indices
                    assert any(r.exists for r in got)
        assert flight_recorder.mid_request_compiles() == compiles
        assert sum(flight_recorder.fallbacks_by_site().values()) == fallbacks
        # plain all-dataset requests rode the mesh stack's columns; the
        # selected ones fanned out, one launch on every owner
        assert eng.mesh_searches - mesh_before == 3
        launched = {
            chip: n - by_chip.get(chip, 0)
            for chip, n in flight_recorder.launches_by_chip().items()
        }
        owners = {str(row["chip"]) for row in eng.placement_table()}
        assert len(owners) == 4
        for chip in owners:
            assert launched.get(chip, 0) >= 3, (chip, launched)
        # the stack carries columns only: the planes are resident once
        assert not any(k.startswith("plane") for k in eng._mesh_state[2])
        kinds = {kind for (_c, kind) in eng.resident_bytes()}
        assert kinds == {"tiles", "planes", "stack"}
    finally:
        eng.close()


def test_add_index_leaves_the_planes_on_their_owner(chips):
    """``add_index`` uploads on the publishing thread, before or after
    warm-up alike: when it returns the key's planes lie on its owner
    and a search reads them there, with nothing to wait for."""
    chips(4)
    eng, shards = _engine(n_datasets=0, use_mesh=False)
    try:
        for d in range(4):
            shard = _shard(d)
            shards.append(shard)
            eng.add_index(shard)
            dindex, planes = eng._indexes[_key(shard)][1:]
            assert planes is not None and planes.device is dindex.device
        assert eng.plane_ledger()["reservedBytes"] == 0
        pay = _payload(shards[0], [], selected=True)
        got, want = eng.search(pay), _reference(shards, pay)
        assert [r.sample_indices for r in got] == [r.sample_indices for r in want]
    finally:
        eng.close()


@pytest.mark.parametrize("use_mesh", [False, True])
def test_planes_read_from_the_host_are_a_counted_fall_back(chips, use_mesh):
    """Planes that should lie on their owner and do not (here the
    chip's budget declines every one): a request that reads them is
    answered from the host's copy, the same answers, and COUNTED as
    ``device.fallbacks{host_planes}`` once a request, so the
    benchmark's ``correct`` sees a deployment whose planes fell off
    their chips; a request that reads no planes counts nothing, and a
    republish that fits clears the key."""
    chips(4)
    eng, shards = _engine(use_mesh=use_mesh, plane_hbm_budget_gb=1e-9)
    try:
        assert all(p is None for _k, _s, p in eng.index_snapshot())
        assert eng._planes_declined == {_key(s) for s in shards}
        site = lambda: flight_recorder.fallbacks_by_site().get("host_planes", 0)
        before = site()
        for n, datasets in enumerate(([], ["pl2"]), start=1):
            pay = _payload(shards[0], datasets, selected=True)
            got, want = eng.search(pay), _reference(shards, pay)
            assert [r.sample_indices for r in got] == [
                r.sample_indices for r in want]
            assert [r.call_count for r in got] == [r.call_count for r in want]
            assert site() == before + n
        eng.search(_payload(shards[0], [], selected=False))
        assert site() == before + 2
        # the budget back: the republished key's planes fit, its
        # requests count no more; the others' still do
        eng.config = dataclasses.replace(
            eng.config,
            engine=dataclasses.replace(
                eng.config.engine, plane_hbm_budget_gb=11.0
            ),
        )
        eng.add_index(_shard(2))
        assert _key(shards[2]) not in eng._planes_declined
        eng.search(_payload(shards[0], ["pl2"], selected=True))
        assert site() == before + 2
        eng.search(_payload(shards[0], ["pl1"], selected=True))
        assert site() == before + 3
    finally:
        eng.close()


def test_device_planes_off_is_no_fall_back(chips):
    """An engine told to keep its planes on the host
    (``device_planes`` off) declined nothing: its plane reads count no
    fall-back."""
    chips(4)
    eng, shards = _engine(device_planes=False)
    try:
        assert not eng._planes_declined
        before = sum(flight_recorder.fallbacks_by_site().values())
        pay = _payload(shards[0], [], selected=True)
        got, want = eng.search(pay), _reference(shards, pay)
        assert [r.sample_indices for r in got] == [r.sample_indices for r in want]
        assert sum(flight_recorder.fallbacks_by_site().values()) == before
    finally:
        eng.close()


def test_term_descendants_from_many_threads(tmp_path):
    """One sqlite connection, sixteen threads: every answer equal to the
    single-threaded one, no exception (the unlocked connection failed
    1-13 requests of 3000 with 500: PERF.md)."""
    import sys
    import threading

    from sbeacon_tpu.metadata.ontology import OntologyStore

    store = OntologyStore(tmp_path / "onto.sqlite")
    rng = random.Random(3)
    terms = [f"T:{i:04d}" for i in range(200)]
    store.register_edges(
        (terms[i], terms[rng.randrange(i)]) for i in range(1, len(terms))
    )
    want = {t: store.term_descendants(t) for t in terms}
    want["T:none"] = {"T:none"}
    asked = list(want)
    errors: list = []
    wrong: list = []

    def worker(k):
        mine = random.Random(k)
        try:
            for _ in range(500):
                t = mine.choice(asked)
                if store.term_descendants(t) != want[t]:
                    wrong.append(t)
        except Exception as e:  # the failure this test exists for
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and wrong == []
    store.close()


def test_label_spread_reader(monkeypatch):
    """The benchmark's reader of ``chip_launch_balance`` and
    ``chip_resident_gb_max``: least over largest of a counter's window
    difference, the largest of a gauge, nothing where the program has
    no such series."""
    import importlib.util
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "benchmark"
    monkeypatch.syspath_prepend(str(bench))  # readers import their siblings
    path = bench / "readers/label_spread.py"
    spec = importlib.util.spec_from_file_location("label_spread", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = {
        "before": {"metrics": {"device": {"launches_by_chip": {"0": 10, "1": 10}}}},
        "after": {"metrics": {"device": {
            "launches_by_chip": {"0": 110, "1": 90, "2": 100},
            "resident_bytes": {"0": {"tiles": 1e9, "planes": 10e9},
                               "1": {"tiles": 1e9, "planes": 10e9, "stack": 5e8}},
        }}},
    }
    balance = {"path": "device.launches_by_chip", "delta": True,
               "what": "min_over_max", "scale": 100}
    assert reader.read(balance, ctx) == pytest.approx(80.0)
    fullest = {"path": "device.resident_bytes", "delta": False,
               "what": "max", "scale": 1e-9}
    assert reader.read(fullest, ctx) == pytest.approx(11.5)
    parent = {"before": {"metrics": {}}, "after": {"metrics": {"device": {}}}}
    assert reader.read(balance, parent) is None
    assert reader.read(fullest, parent) is None
    idle = {"before": ctx["after"], "after": ctx["after"]}
    assert reader.read(balance, idle) is None


def _pack_reference(shard, tile=128):
    """The one-pass packing ``pack_tiles`` replaced, kept as its spec."""
    from sbeacon_tpu.ops import scatter_kernel as sk

    n, c = shard.n_rows, shard.cols
    n_tiles = n // tile + 1 + sk.ScatterDeviceIndex.MAX_C
    packed = np.empty((sk.N_PACKED, n_tiles * tile), dtype=np.int32)

    def fill(row, values, pad):
        packed[row, :n] = values
        packed[row, n:] = pad

    fill(sk.P_POS, c["pos"], sk._PAD_FILLS["pos"])
    fill(sk.P_REC_END, c["rec_end"], sk._PAD_FILLS["rec_end"])
    fill(sk.P_REF_HASH, c["ref_hash"], 0)
    fill(sk.P_ALT_HASH, c["alt_hash"], 0)
    lens = np.minimum(c["alt_len"].astype(np.int64), sk._ALT_LEN_CLAMP) | (
        np.minimum(c["ref_len"].astype(np.int64), sk._REF_LEN_CLAMP) << 16
    )
    fill(sk.P_LENS, lens.astype(np.int32), 0)
    flags = sk.stage_symbolic_flags(c["flags"], c["alt_prefix"])
    flags |= np.clip(c["ref_repeat_k"].astype(np.int64) + 1, 0, 127) << 19
    clamped = (c["ref_len"].astype(np.int64) > sk._REF_LEN_CLAMP) | (
        c["alt_len"].astype(np.int64) > sk._ALT_LEN_CLAMP
    )
    flags |= np.where(clamped, np.int64(sk.ROW_CLAMPED), 0)
    same = np.zeros(n, dtype=np.int64)
    same[1:] = c["rec_id"][1:] == c["rec_id"][:-1]
    flags |= same * sk.SAME_PREV
    fill(sk.P_FLAGS, flags.astype(np.int32), 0)
    fill(sk.P_AC, c["ac"], 0)
    fill(sk.P_AN, c["an"], 0)
    z = np.flatnonzero(np.concatenate(([0], same.astype(np.int8), [0])) == 0)
    tiles = packed.reshape(sk.N_PACKED, n_tiles, tile).transpose(1, 0, 2)
    return tiles, int(np.diff(z).max()) - 1


@pytest.mark.parametrize("block_rows", [256, 1024, 1 << 20])
def test_tiles_packed_in_blocks_equal_the_one_pass_packing(monkeypatch, block_rows):
    """Blocks on threads, edges inside records and inside the padding:
    the same tiles bit for bit, the same longest record."""
    from sbeacon_tpu.ops import scatter_kernel as sk

    monkeypatch.setattr(sk, "PACK_BLOCK_ROWS", block_rows)
    shard = synthetic_shard(
        5000, n_samples=8, seed=21, dataset_id="pk", p_multiallelic=0.4,
    )
    want_tiles, want_k = _pack_reference(shard)
    got = sk.ScatterDeviceIndex(shard)
    assert got.seg_k == want_k and want_k >= 1
    assert got.tiles.shape == want_tiles.shape
    assert np.array_equal(np.asarray(got.tiles), want_tiles)
