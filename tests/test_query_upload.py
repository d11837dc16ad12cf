"""One upload a launch: the XLA-gather programs and the engine's mesh
program take their encoded query batch as ONE packed ``int32`` array
(``ops/kernel.pack_queries`` on the host, ``unpack_queries`` in the
program).

- pack -> unpack gives back every field of ``encode_queries`` bit for
  bit, in a program as on the host;
- the packed program's answers are those of ``jax.vmap(_query_one)`` over
  the dictionary, leaf for leaf, for both index classes and every query
  class;
- a launch through ``run_queries`` and one through ``sharded_query`` make
  ONE host-to-device put and tick ``device.query_uploads`` by exactly 1.
"""

import random
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sbeacon_tpu.index import build_index
from sbeacon_tpu.index.columnar import FLAG, INT32_MAX
from sbeacon_tpu.ops import kernel as kernel_mod
from sbeacon_tpu.ops.kernel import (
    PACK_INT_FIELDS,
    PACK_WIDTH,
    DeviceIndex,
    FusedDeviceIndex,
    QuerySpec,
    _query_batch,
    _query_one,
    encode_queries,
    pack_queries,
    padded_batch,
    run_queries,
    unpack_queries,
)
from sbeacon_tpu.parallel import mesh as mesh_mod
from sbeacon_tpu.parallel.mesh import StackedIndex, make_mesh, sharded_query
from sbeacon_tpu.telemetry import flight_recorder
from sbeacon_tpu.testing import random_records

SAMPLES = [f"S{i}" for i in range(4)]


@pytest.fixture(scope="module")
def shards():
    out = []
    for d in range(3):
        rng = random.Random(4200 + d)
        recs = random_records(
            rng, chrom="1", n=500 + 60 * d, n_samples=len(SAMPLES),
            p_symbolic=0.2, p_multiallelic=0.3,
        )
        recs += random_records(
            rng, chrom="22", n=200, n_samples=len(SAMPLES), p_symbolic=0.1
        )
        out.append(build_index(recs, dataset_id=f"d{d}", sample_names=SAMPLES))
    return out


def _queries(shard, kind: str) -> list[QuerySpec]:
    """A dozen queries of one class, aimed at rows the shard holds."""
    rng = random.Random(f"queries of kind {kind}")
    cols = shard.cols
    lo, hi = shard.chrom_offsets[1], shard.chrom_offsets[2]  # chromosome 1
    # single-base alts, so that alternate_bases "N" finds the row itself
    single = np.flatnonzero(cols["flags"][lo:hi] & FLAG.SINGLE_BASE) + lo
    picks = [int(r) for r in rng.sample(list(single), 12)]
    far = 1 << 30
    if kind == "point":
        return [
            QuerySpec("1", int(cols["pos"][r]), int(cols["pos"][r]), 1, far,
                      alternate_bases="N")
            for r in picks
        ]
    if kind == "range":
        return [
            QuerySpec("1", max(1, int(cols["pos"][r]) - 3000),
                      int(cols["pos"][r]) + 3000, 1, far,
                      reference_bases="N", alternate_bases="N")
            for r in picks
        ]
    if kind == "bracket":
        return [
            QuerySpec("1", max(1, int(cols["pos"][r]) - 500),
                      int(cols["pos"][r]) + 500,
                      int(cols["rec_end"][r]) - 2, int(cols["rec_end"][r]) + 500,
                      alternate_bases="N")
            for r in picks
        ]
    if kind == "by-type":
        types = ["DEL", "INS", "DUP", "DUP:TANDEM", "CNV", None]
        return [
            QuerySpec("1", max(1, int(cols["pos"][r]) - 20000),
                      int(cols["pos"][r]) + 20000, 1, far,
                      variant_type=types[i % len(types)],
                      variant_min_length=i % 3,
                      variant_max_length=-1 if i % 2 else 50)
            for i, r in enumerate(picks)
        ]
    assert kind == "symbolic"
    # a type no code names: matched by the alt's first bytes alone
    return [
        QuerySpec("1", 1, far, 1, far, variant_type=vt)
        for vt in ("INV", "DEL:ME", "CN0", "CN2", "DUP", "NO_SUCH_TYPE")
    ] + [
        QuerySpec("22", 1, far, 1, far, variant_type="DEL",
                  reference_bases="N"),
    ]


KINDS = ("point", "range", "bracket", "by-type", "symbolic")

# -- pack -> unpack, bit for bit ----------------------------------------------


def _assert_round_trip(enc: dict, packed: np.ndarray) -> None:
    assert packed.dtype == np.int32 and packed.shape[1] == PACK_WIDTH
    got = jax.jit(unpack_queries)(packed)
    assert set(got) == set(enc) | {"shard"}
    for name, want in enc.items():
        leaf = np.asarray(got[name])
        assert leaf.dtype == want.dtype, name
        assert leaf.shape == want.shape, name
        assert leaf.tobytes() == want.tobytes(), name
    if "shard" not in enc:
        assert not np.asarray(got["shard"]).any()


class _Puts:
    """Counts the calls of a module's one upload function."""

    def __init__(self, monkeypatch, holder, name):
        self.calls = []
        real = getattr(holder, name)

        def spy(x, *args, **kw):
            self.calls.append((x, args, kw))
            return real(x, *args, **kw)

        monkeypatch.setattr(holder, name, spy)


def test_the_row_has_every_field_once():
    enc = encode_queries([QuerySpec("1", 1, 2, 1, 2)], [0])
    words = {"ref_wild": 1, "shard": 1, "vprefix": 4, "vprefix_mask": 4}
    assert set(PACK_INT_FIELDS) | set(words) == set(enc)
    assert PACK_WIDTH == len(PACK_INT_FIELDS) + sum(words.values()) == 23
    for name in PACK_INT_FIELDS:
        assert enc[name].dtype == np.int32, name


@pytest.mark.parametrize(
    "case",
    ["prefix_top_bit", "ref_wild", "shard", "max_len_int32_max", "no_shard"],
)
def test_pack_then_unpack_is_the_encoding(case):
    far = int(INT32_MAX)
    queries = [
        QuerySpec("1", 1, far, 1, far, variant_type="DEL"),
        QuerySpec("X", 5, 9, 5, 9, reference_bases="ACG", alternate_bases="T"),
        QuerySpec("22", 7, 7, 1, far, alternate_bases="N",
                  variant_min_length=3, variant_max_length=40),
    ]
    enc = encode_queries(
        queries, None if case == "no_shard" else [2, 0, 511]
    )
    if case == "prefix_top_bit":
        # bytes over 0x7f in every word, and the mask's words are all ones
        # wherever the prefix is four bytes long
        enc["vprefix"][0] = [0x80000000, 0xFFFFFFFF, 0xDEADBEEF, 0x7FFFFFFF]
        enc["vprefix_mask"][0] = [0xFFFFFFFF, 0xFFFFFFFF, 0xFF000000, 0]
        assert (pack_queries(enc)[0, -8:-4] < 0).sum() == 3
    elif case == "ref_wild":
        assert enc["ref_wild"].tolist() == [True, False, True]
        # a hash with the top bit set stays what it was
        enc["ref_hash"][1] = -(2**31)
        enc["alt_hash"][1] = -1
    elif case == "shard":
        assert pack_queries(enc)[:, len(PACK_INT_FIELDS) + 1].tolist() == [2, 0, 511]
    elif case == "max_len_int32_max":
        assert enc["max_len"].tolist() == [far, far, 40]
        assert enc["start_max"][0] == far
    _assert_round_trip(enc, pack_queries(enc))


@pytest.mark.parametrize("b", [1, 3, 8, 9, 33])
def test_a_padded_rung_repeats_query_zero(b, shards, monkeypatch):
    """The rows ``run_queries`` adds to fill a rung are query 0's, as
    the dictionary's were."""
    dindex = DeviceIndex(shards[0], pad_unit=1024)
    rng = random.Random(b)
    queries = [
        QuerySpec("1", rng.randrange(1, 10**6), 10**6, 1, 10**9,
                  variant_type=rng.choice(["DEL", "INS", None]))
        for _ in range(b)
    ]
    enc = encode_queries(queries)
    puts = _Puts(monkeypatch, kernel_mod.jnp, "asarray")
    run_queries(dindex, enc, window_cap=256, record_cap=16)
    ((uploaded, _args, _kw),) = puts.calls
    rung = padded_batch(dindex, b)
    assert uploaded.shape == (rung, PACK_WIDTH) and rung >= b
    want = {
        k: np.concatenate([v, np.repeat(v[:1], rung - b, axis=0)])
        for k, v in enc.items()
    }
    _assert_round_trip(want, uploaded)


# -- the packed program against vmap(_query_one) over the dictionary ----------


@pytest.fixture(scope="module")
def indexes(shards):
    """Each index class with the shard count its batches name (None:
    a single-shard index takes no shard ids)."""
    return {
        "DeviceIndex": (DeviceIndex(shards[0], pad_unit=1024), None),
        "FusedDeviceIndex": (FusedDeviceIndex(shards, pad_unit=1024), len(shards)),
    }


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("which", ["DeviceIndex", "FusedDeviceIndex"])
def test_the_packed_program_answers_as_the_dictionary_did(
    indexes, shards, which, kind
):
    dindex, k = indexes[which]
    queries = _queries(shards[0], kind)
    shard_ids = None if k is None else [i % k for i in range(len(queries))]
    enc = encode_queries(queries, shard_ids)
    statics = dict(window_cap=1024, record_cap=64, n_iters=dindex.n_iters)
    want = jax.jit(
        lambda arrays, q: jax.vmap(partial(_query_one, arrays, **statics))(q)
    )(dindex.arrays, {name: jnp.asarray(v) for name, v in enc.items()})
    got = _query_batch(dindex.arrays, jnp.asarray(pack_queries(enc)), **statics)
    assert set(got) == set(want)
    for leaf in want:
        a, b = np.asarray(got[leaf]), np.asarray(want[leaf])
        assert a.dtype == b.dtype and a.shape == b.shape, leaf
        assert a.tobytes() == b.tobytes(), (which, kind, leaf)
    if kind != "symbolic":
        assert np.asarray(want["n_matched"]).sum() > 0, "a vacuous comparison"
    # ... and through the seam, trimmed to the batch
    res = run_queries(dindex, enc, window_cap=1024, record_cap=64)
    for leaf in ("exists", "call_count", "n_variants", "all_alleles_count",
                 "n_matched", "overflow", "rows"):
        assert np.array_equal(getattr(res, leaf), np.asarray(want[leaf])), leaf


# -- one put a launch, counted -------------------------------------------------


def _uploads(family: str) -> int:
    return flight_recorder.query_uploads_by_family().get(family, 0)


@pytest.mark.parametrize(
    "which,as_list",
    # a fused batch arrives encoded, with its shard ids: no list there
    [("DeviceIndex", False), ("DeviceIndex", True), ("FusedDeviceIndex", False)],
    ids=["device_index-dict", "device_index-list", "fused-dict"],
)
def test_run_queries_makes_one_put_and_counts_it(
    indexes, shards, monkeypatch, which, as_list
):
    dindex, k = indexes[which]
    queries = _queries(shards[0], "range")[:5]
    batch = queries if as_list else encode_queries(
        queries, None if k is None else [0] * len(queries)
    )
    run_queries(dindex, batch, window_cap=512, record_cap=32)  # compiled
    puts = _Puts(monkeypatch, kernel_mod.jnp, "asarray")
    uploads0, launches0 = _uploads("fused"), flight_recorder.launches_by_family()["fused"]
    donated0 = flight_recorder.donated_buffers
    # nothing but the one explicit put may cross to the device: a numpy
    # leaf handed to the jitted call would be an upload of its own
    with jax.transfer_guard_host_to_device("disallow"):
        res = run_queries(dindex, batch, window_cap=512, record_cap=32)
    assert len(res.exists) == len(queries)
    assert len(puts.calls) == 1
    (x, _args, _kw) = puts.calls[0]
    assert isinstance(x, np.ndarray) and x.dtype == np.int32
    assert x.shape == (padded_batch(dindex, len(queries)), PACK_WIDTH)
    assert _uploads("fused") == uploads0 + 1
    assert flight_recorder.launches_by_family()["fused"] == launches0 + 1
    assert flight_recorder.donated_buffers == donated0 + 1
    entry = flight_recorder.snapshot()["ring"]["entries"][-1]
    assert entry["family"] == "fused" and entry["uploads"] == 1
    assert entry["donated"] == 1


def test_an_l0_launch_counts_under_its_own_family(shards):
    from sbeacon_tpu.ops.kernel import L0DeviceIndex

    l0 = L0DeviceIndex(shards, pad_unit=1024)
    enc = encode_queries(_queries(shards[0], "point")[:3], [0, 1, 2])
    before, fused0 = _uploads("fused_l0"), _uploads("fused")
    run_queries(l0, enc, window_cap=512, record_cap=32)
    assert _uploads("fused_l0") == before + 1 and _uploads("fused") == fused0


@pytest.fixture(scope="module")
def four_chips(shards):
    """The engine's mesh stack on a four-device forced-host mesh."""
    mesh = make_mesh(devices=jax.devices()[:4])
    assert int(mesh.devices.size) == 4
    stack = StackedIndex(shards, n_datasets_padded=4)
    return mesh, stack, stack.shard_to_mesh(mesh)


def test_sharded_query_makes_one_replicated_put_and_counts_it(
    four_chips, shards, monkeypatch
):
    mesh, stack, arrays = four_chips
    queries = _queries(shards[0], "range")[:1]
    run = partial(
        sharded_query, arrays, mesh=mesh, n_iters=stack.n_iters,
        window_cap=512, record_cap=32, n_datasets=stack.n_datasets,
    )
    run(queries)  # compiled
    puts = _Puts(monkeypatch, mesh_mod.jax, "device_put")
    by_key = _Puts(monkeypatch, mesh_mod.jnp, "asarray")
    uploads0 = _uploads("mesh")
    launches0 = flight_recorder.launches_by_family()["mesh"]
    with jax.transfer_guard_host_to_device("disallow"):
        per_ds, agg = run(queries)
    assert len(puts.calls) == 1 and not by_key.calls
    (x, args, _kw) = puts.calls[0]
    assert isinstance(x, np.ndarray) and x.shape == (1, PACK_WIDTH)
    assert args == (NamedSharding(mesh, P()),)
    assert _uploads("mesh") == uploads0 + 1
    assert flight_recorder.launches_by_family()["mesh"] == launches0 + 1
    assert flight_recorder.snapshot()["ring"]["entries"][-1]["uploads"] == 1
    assert flight_recorder.launch_summary()["queryUploads"]["mesh"] == uploads0 + 1
    # the answers are the one-chip program's, dataset by dataset
    for d, shard in enumerate(shards):
        one = run_queries(
            DeviceIndex(shard, pad_unit=1024), queries,
            window_cap=512, record_cap=32,
        )
        for leaf in ("call_count", "all_alleles_count", "n_variants", "n_matched"):
            assert int(per_ds[leaf][d, 0]) == int(getattr(one, leaf)[0]), (d, leaf)
    assert int(agg["call_count"][0]) == int(per_ds["call_count"][:, 0].sum()) > 0


def test_the_put_lands_whole_on_every_chip(four_chips):
    mesh, _stack, _arrays = four_chips
    packed = pack_queries(encode_queries([QuerySpec("1", 1, 2, 1, 2)]))
    put = jax.device_put(packed, NamedSharding(mesh, P()))
    assert put.is_fully_replicated and len(put.addressable_shards) == 4
    assert all(s.data.shape == packed.shape for s in put.addressable_shards)


def test_metrics_serve_the_counter_by_family():
    from sbeacon_tpu.telemetry import MetricsRegistry, register_device_metrics

    registry = MetricsRegistry()
    register_device_metrics(registry)
    served = registry.render_json()["device"]["query_uploads"]
    assert served == flight_recorder.query_uploads_by_family()
    assert served.get("fused", 0) >= 1
