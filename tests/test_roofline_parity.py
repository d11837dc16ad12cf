"""Roofline-campaign parity (ISSUE 17): the adaptive tier ladder is
a PURE perf change — every answer must stay byte-identical
(``dataclasses.asdict``) to the legacy ``BATCH_TIERS`` ladder, across
boolean/count/record x selected-samples x delta-tail (L0) shapes.

The ladder tests flip the process-global active ladder around the
SAME index objects, so any divergence is the ladder's padding and
nothing else. Tier-1 safe (8 forced host devices via conftest).
"""

import dataclasses
import random

import numpy as np
import pytest

from sbeacon_tpu.config import BeaconConfig, EngineConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import (
    BATCH_TIERS,
    FusedDeviceIndex,
    L0DeviceIndex,
    QuerySpec,
    TierLadder,
    active_ladder,
    encode_queries,
    run_queries,
    set_active_ladder,
)
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records

SAMPLES = ["S0", "S1"]


def _shards(n=3, chrom="1", rows=200, seed=70):
    return [
        build_index(
            random_records(
                random.Random(seed + d), chrom=chrom, n=rows, n_samples=2
            ),
            dataset_id=f"d{d}",
            vcf_location=f"v{d}",
            sample_names=SAMPLES,
        )
        for d in range(n)
    ]


def _assert_results_byte_identical(a, b, label=""):
    """dataclasses.asdict equality down to dtype and raw bytes — a
    perf knob changing even a dtype would silently change response
    payload sizes downstream."""
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    assert da.keys() == db.keys(), label
    for k in da:
        va, vb = da[k], db[k]
        if va is None or vb is None:
            assert va is vb, (label, k)
            continue
        na, nb = np.asarray(va), np.asarray(vb)
        assert na.dtype == nb.dtype, (label, k, na.dtype, nb.dtype)
        assert na.shape == nb.shape, (label, k, na.shape, nb.shape)
        assert na.tobytes() == nb.tobytes(), (label, k)


def _legacy_ladder():
    return TierLadder(BATCH_TIERS, source="test-legacy")


# -- fit() convergence --------------------------------------------------------


def test_ladder_fit_skips_skew_and_floor_and_converges():
    """fit() must never chase waste it cannot fix: the bottom rung's
    padding is the floor's known cost (a sub-floor rung would leak
    process-wide — every 3-query batch padding to 4 instead of 8), and
    a ``plane`` launch's tier is its launch group's slot count, its
    padding the members a request did not ask: no batch rung fixes
    that. Both classes of cell must be ignored, and
    re-fitting on the same histogram must be a fixed point — otherwise
    each engine warmup() refit grows the ladder again."""
    ladder = TierLadder(TierLadder.DEFAULT_RUNGS)
    # sub-floor: 8 is the bottom rung, so an 87%-waste cell at 8 stays
    assert ladder.fit({("fused", 8): (10, 80)}) is ladder
    # a family padded by something other than a rung: never a split
    assert ladder.fit({("plane", 16): (16, 1280)}) is ladder
    assert ladder.fit({("plane", 512): (650, 5120)}) is ladder
    # a genuinely wasteful serving rung splits once...
    fitted = ladder.fit({("fused", 512): (650, 5120)})
    assert 256 in fitted.rungs and fitted.source == "fit"
    # ...and the same histogram is then a fixed point (idempotent
    # warmup: warmup -> refit -> warmup must not compile new programs)
    assert fitted.fit({("fused", 512): (650, 5120)}) is fitted


# -- adaptive ladder vs legacy BATCH_TIERS ------------------------------------


@pytest.mark.parametrize(
    "cls", [FusedDeviceIndex, L0DeviceIndex], ids=["fused", "l0"]
)
def test_ladder_parity_byte_identical_kernel(cls):
    """Odd batch sizes straddling the new rungs (3 -> 8, 9 -> 16,
    33 -> 64) pad differently under the adaptive ladder than under
    legacy (9 -> 64, 33 -> 64) — the answers must not notice, on the
    base fused stack AND the L0 delta-tail mini-index (whose padded
    segment-table shape is the delta-tail program signature)."""
    shards = _shards()
    dindex = cls(shards)
    specs = [
        QuerySpec("1", 1, 1 << 29, 1, 1 << 30, alternate_bases="N"),
        QuerySpec("1", 500, 1500, 1, 1 << 30, alternate_bases="N"),
        QuerySpec("1", 1, 1 << 29, 1, 1 << 30, alternate_bases="T"),
    ]
    pairs = [(sp, sid) for sp in specs for sid in range(len(shards))]

    def run_all():
        out = []
        for b in (3, 9, 33):
            batch = (pairs * ((b // len(pairs)) + 1))[:b]
            enc = encode_queries(
                [sp for sp, _ in batch],
                shard_ids=[sid for _, sid in batch],
            )
            out.append(
                run_queries(dindex, enc, window_cap=2048, record_cap=64)
            )
        return out

    set_active_ladder(_legacy_ladder())
    try:
        legacy = run_all()
    finally:
        set_active_ladder(None)
    adaptive = run_all()
    for b, la, ad in zip((3, 9, 33), legacy, adaptive):
        _assert_results_byte_identical(la, ad, label=f"b={b}")


def test_ladder_parity_byte_identical_engine_granularities():
    """Engine-level: boolean/count/record x selected-samples payloads
    answer byte-identically under the adaptive and legacy ladders —
    the serving micro-batcher, host materialisation and response
    shaping all sit downstream of the pad seam the ladder moved."""
    eng = VariantEngine(
        BeaconConfig(
            engine=EngineConfig(
                use_mesh=False,
                microbatch_wait_ms=0.0,
                response_cache=False,
            )
        )
    )
    for s in _shards():
        eng.add_index(s)
    try:
        payloads = []
        for gran in ("boolean", "count", "record"):
            payloads.append(
                VariantQueryPayload(
                    dataset_ids=[f"d{d}" for d in range(3)],
                    reference_name="1",
                    start_min=1,
                    start_max=1 << 29,
                    end_min=1,
                    end_max=1 << 30,
                    alternate_bases="N",
                    requested_granularity=gran,
                    include_datasets="HIT",
                )
            )
        sel = VariantQueryPayload(
            dataset_ids=[f"d{d}" for d in range(3)],
            reference_name="1",
            start_min=1,
            start_max=1 << 29,
            end_min=1,
            end_max=1 << 30,
            alternate_bases="N",
            requested_granularity="record",
            include_datasets="HIT",
            selected_samples_only=True,
            sample_names={f"d{d}": ["S0"] for d in range(3)},
        )
        payloads.append(sel)
        set_active_ladder(_legacy_ladder())
        try:
            legacy = [
                [dataclasses.asdict(r) for r in eng.search(q)]
                for q in payloads
            ]
        finally:
            set_active_ladder(None)
        adaptive = [
            [dataclasses.asdict(r) for r in eng.search(q)]
            for q in payloads
        ]
        for q, la, ad in zip(payloads, legacy, adaptive):
            assert la == ad, q.requested_granularity
    finally:
        eng.close()


def test_adaptive_ladder_halves_worst_padding_waste_without_compiles(
    monkeypatch,
):
    """Coalesced bursts landing between the legacy 8 and 64 rungs
    (9..60 all pad to 64 under ``BATCH_TIERS``) are the traffic the
    adaptive ladder exists for: with every active rung warmed first,
    the worst (family, tier) padding-waste cell at least halves on the
    same bursts, and neither ladder compiles inside a request."""
    import sbeacon_tpu.telemetry as tel

    shards = _shards(4, rows=300, seed=2100)
    findex = FusedDeviceIndex(shards)
    specs = [
        QuerySpec("1", 1, 1 << 29, 1, 1 << 30, alternate_bases="N"),
        QuerySpec("1", 500, 2500, 1, 1 << 30, alternate_bases="N"),
        QuerySpec("1", 1, 1 << 29, 1, 1 << 30, alternate_bases="T"),
    ]

    def run(b):
        enc = encode_queries(
            [specs[i % len(specs)] for i in range(b)],
            shard_ids=[i % len(shards) for i in range(b)],
        )
        run_queries(findex, enc, window_cap=512, record_cap=64)

    def leg(ladder):
        rec = tel.DeviceFlightRecorder(ring_size=64)
        monkeypatch.setattr(tel, "flight_recorder", rec)
        set_active_ladder(ladder)
        try:
            with tel.device_warmup_phase():
                for rung in active_ladder().rungs:
                    run(rung)
            for b in (9, 12, 14, 16, 20, 28, 48, 60):
                run(b)
        finally:
            set_active_ladder(None)
        assert rec.mid_request_compiles() == 0, (
            ladder, rec.last_mid_request_compile())
        return rec.worst_pad_waste()["waste"]

    legacy = leg(_legacy_ladder())
    adaptive = leg(None)
    assert adaptive <= legacy / 2, (legacy, adaptive)


def test_ladder_parity_delta_tail():
    """Delta-tail shapes: a base shard plus a raw delta tail answers
    byte-identically under both ladders — the per-target delta path
    and the L0 stacking both pad batches through the same ladder."""
    recs = random_records(random.Random(81), chrom="1", n=240, n_samples=2)

    def build():
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(
                    use_mesh=False,
                    microbatch_wait_ms=0.0,
                    response_cache=False,
                )
            )
        )
        eng.add_index(
            build_index(
                recs[:160],
                dataset_id="dsA",
                vcf_location="a.vcf",
                sample_names=SAMPLES,
            )
        )
        for lo in (160, 200):
            eng.add_delta(
                build_index(
                    recs[lo : lo + 40],
                    dataset_id="dsA",
                    vcf_location="a.vcf",
                    sample_names=SAMPLES,
                )
            )
        return eng

    q = VariantQueryPayload(
        dataset_ids=["dsA"],
        reference_name="1",
        start_min=1,
        start_max=1 << 29,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity="record",
        include_datasets="HIT",
    )
    eng = build()
    try:
        set_active_ladder(_legacy_ladder())
        try:
            legacy = [dataclasses.asdict(r) for r in eng.search(q)]
        finally:
            set_active_ladder(None)
        adaptive = [dataclasses.asdict(r) for r in eng.search(q)]
        assert legacy == adaptive
    finally:
        eng.close()
