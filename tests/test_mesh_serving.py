"""Mesh serving path: multi-dataset queries through the dataset-sharded
StackedIndex + psum fan-in as ONE pjit program (VariantEngine._mesh_search),
asserted equal to the thread-scatter path and to the CPU oracle, end-to-end
through BeaconApp. (Reference mapping: variantutils/search_variants.py:77-155
scatter/fan-in collapsed into one compiled dispatch.)

The conftest pins 8 virtual CPU devices, so the mesh path engages by default
for every multi-dataset engine in the suite; this file pins down the
contract explicitly.
"""

import random

import numpy as np
import pytest

from sbeacon_tpu.config import BeaconConfig, EngineConfig, StorageConfig
from sbeacon_tpu.engine import VariantEngine
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records

SAMPLES = ["S0", "S1", "S2"]


def _engines(n_ds=5, *, n=400, seed0=300, **eng_over):
    """(mesh_engine, scatter_engine) over identical shard sets."""
    out = []
    for use_mesh in (True, False):
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(
                    microbatch=False, use_mesh=use_mesh, **eng_over
                )
            )
        )
        for d in range(n_ds):
            rng = random.Random(seed0 + d)
            recs = random_records(rng, chrom="7", n=n, n_samples=len(SAMPLES))
            eng.add_index(
                build_index(
                    recs,
                    dataset_id=f"d{d}",
                    vcf_location=f"v{d}.vcf.gz",
                    sample_names=SAMPLES,
                )
            )
        out.append(eng)
    return out


def _payload(**kw):
    base = dict(
        dataset_ids=[],
        reference_name="7",
        start_min=1,
        start_max=1 << 30,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        include_datasets="HIT",
        requested_granularity="record",
    )
    base.update(kw)
    return VariantQueryPayload(**base)


def _assert_same(rm, rt):
    assert len(rm) == len(rt)
    for a, b in zip(rm, rt):
        assert (a.dataset_id, a.vcf_location) == (b.dataset_id, b.vcf_location)
        assert a.exists == b.exists
        assert a.call_count == b.call_count
        assert a.all_alleles_count == b.all_alleles_count
        assert a.variants == b.variants
        assert a.sample_indices == b.sample_indices


def test_mesh_engages_and_matches_scatter():
    em, et = _engines()
    pay = _payload()
    rm, rt = em.search(pay), et.search(pay)
    assert em.mesh_searches == 1 and et.mesh_searches == 0
    _assert_same(rm, rt)
    assert any(r.exists for r in rm)


def test_mesh_dataset_subset_and_single_target():
    em, et = _engines()
    pay = _payload(dataset_ids=["d1", "d3"])
    _assert_same(em.search(pay), et.search(pay))
    assert em.mesh_searches == 1
    # single-target queries stay on the scatter/batched path
    pay1 = _payload(dataset_ids=["d2"])
    _assert_same(em.search(pay1), et.search(pay1))
    assert em.mesh_searches == 1


def test_mesh_overflow_falls_back_to_host_rows():
    # tiny caps force window overflow on broad queries: per-dataset rows
    # must then come from the uncapped host matcher, identical to scatter
    em, et = _engines(window_cap=16, record_cap=8)
    pay = _payload()
    rm, rt = em.search(pay), et.search(pay)
    assert em.mesh_searches == 1
    _assert_same(rm, rt)
    # the corpus has far more than 8 variants per dataset: fallback proved
    assert sum(len(r.variants) for r in rm) > 8


def test_mesh_selected_samples_parity():
    em, et = _engines()
    pay = _payload(
        selected_samples_only=True,
        sample_names={f"d{d}": ["S0", "S2"] for d in range(5)},
        include_samples=True,
    )
    rm, rt = em.search(pay), et.search(pay)
    # the planes are resident once, on their datasets' owner chips: a
    # request that reads them fans out to the owners, not to the mesh
    assert em.mesh_searches == 0
    _assert_same(rm, rt)


def test_mesh_point_and_type_queries_parity():
    em, et = _engines()
    rng = random.Random(9)
    shard0 = em._indexes[("d0", "v0.vcf.gz")][0]
    for _ in range(10):
        r = rng.randrange(shard0.n_rows)
        pos = int(shard0.cols["pos"][r])
        pay = _payload(
            start_min=pos,
            start_max=pos,
            alternate_bases=None,
            variant_type=rng.choice(["DEL", "INS", "DUP", "CNV", None]),
        )
        _assert_same(em.search(pay), et.search(pay))


def test_reingestion_invalidates_mesh_stack():
    em, et = _engines(n_ds=3)
    pay = _payload()
    _assert_same(em.search(pay), et.search(pay))
    # add a new dataset: the stack must rebuild and serve it
    rng = random.Random(999)
    recs = random_records(rng, chrom="7", n=200, n_samples=len(SAMPLES))
    for eng in (em, et):
        eng.add_index(
            build_index(
                recs,
                dataset_id="late",
                vcf_location="late.vcf.gz",
                sample_names=SAMPLES,
            )
        )
    rm, rt = em.search(pay), et.search(pay)
    assert {r.dataset_id for r in rm} == {"d0", "d1", "d2", "late"}
    _assert_same(rm, rt)
    assert em.mesh_searches == 2


def test_mesh_vs_oracle_aggregates():
    """Mesh-path responses match the CPU oracle record-by-record."""
    from sbeacon_tpu.oracle import oracle_search

    em, _ = _engines(n_ds=3, n=150)
    pay = _payload(start_min=1, start_max=40_000)
    rm = em.search(pay)
    assert em.mesh_searches == 1
    for d in range(3):
        rng = random.Random(300 + d)
        recs = random_records(rng, chrom="7", n=150, n_samples=len(SAMPLES))
        want = oracle_search(
            recs,
            first_bp=1,
            last_bp=40_000,
            end_min=1,
            end_max=1 << 30,
            reference_bases=None,
            alternate_bases="N",
            requested_granularity="record",
            include_details=True,
            dataset_id=f"d{d}",
            chrom_label="7",
        )
        got = next(r for r in rm if r.dataset_id == f"d{d}")
        assert got.exists == want.exists
        assert got.call_count == want.call_count
        assert got.all_alleles_count == want.all_alleles_count


def test_beacon_app_serves_through_mesh(tmp_path):
    """End-to-end: /submit two datasets, then a /g_variants POST executes
    via the mesh path (engine.mesh_searches increments) with a correct
    Beacon envelope."""
    from sbeacon_tpu.api import BeaconApp
    from sbeacon_tpu.genomics.tabix import ensure_index
    from sbeacon_tpu.genomics.vcf import write_vcf

    config = BeaconConfig(storage=StorageConfig(root=tmp_path / "data"))
    config.storage.ensure()
    app = BeaconApp(config)
    for d in range(2):
        rng = random.Random(70 + d)
        recs = random_records(rng, chrom="22", n=80, n_samples=len(SAMPLES))
        vcf = tmp_path / f"m{d}.vcf.gz"
        write_vcf(vcf, recs, sample_names=SAMPLES)
        ensure_index(vcf)
        status, body = app.handle(
            "POST",
            "/submit",
            body={
                "datasetId": f"m{d}",
                "assemblyId": "GRCh38",
                "vcfLocations": [str(vcf)],
                "dataset": {"id": f"m{d}", "name": f"M{d}"},
                "index": True,
            },
        )
        assert status == 200, body
    before = app.engine.mesh_searches
    status, body = app.handle(
        "POST",
        "/g_variants",
        body={
            "query": {
                "requestedGranularity": "count",
                "requestParameters": {
                    "assemblyId": "GRCh38",
                    "referenceName": "22",
                    "start": [1, 1 << 30],
                    "end": [1, 1 << 30],
                    "alternateBases": "N",
                },
            }
        },
    )
    assert status == 200, body
    assert body["responseSummary"]["exists"] is True
    assert app.engine.mesh_searches == before + 1


def test_concurrent_queries_during_reingestion():
    """Queries racing add_index re-ingestion: no exceptions, and every
    response is internally consistent (the mesh stack snapshot must never
    pair stale arrays with replaced shards — engine._mesh_ready)."""
    import threading

    em, _ = _engines(n_ds=4, n=250)
    pay = _payload()
    stop = threading.Event()
    errors: list[BaseException] = []

    def churn():
        k = 0
        while not stop.is_set():
            rng = random.Random(500 + k)
            recs = random_records(
                rng, chrom="7", n=150 + (k % 3) * 40, n_samples=len(SAMPLES)
            )
            em.add_index(
                build_index(
                    recs,
                    dataset_id=f"d{k % 4}",
                    vcf_location=f"v{k % 4}.vcf.gz",
                    sample_names=SAMPLES,
                )
            )
            k += 1

    def query():
        while not stop.is_set():
            try:
                rs = em.search(pay)
                assert len(rs) == 4
                for r in rs:
                    assert r.call_count >= 0
                    assert r.all_alleles_count >= 0
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=churn)] + [
        threading.Thread(target=query) for _ in range(3)
    ]
    for t in threads:
        t.start()
    import time

    time.sleep(4.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:3]
    # engine still serves correctly after the churn
    rs = em.search(pay)
    assert {r.dataset_id for r in rs} == {"d0", "d1", "d2", "d3"}


def _selected_payload(ds_names, spec, names):
    return VariantQueryPayload(
        dataset_ids=list(ds_names),
        reference_name=spec.chrom,
        start_min=spec.start_min,
        start_max=spec.start_max,
        end_min=1,
        end_max=1 << 30,
        alternate_bases="N",
        requested_granularity="record",
        include_datasets="HIT",
        include_samples=True,
        selected_samples_only=True,
        sample_names={ds: list(names) for ds in ds_names},
    )


def test_selected_samples_on_owner_planes_equal_host_planes():
    """Genotype planes resident once, on their datasets' owner chips:
    a selected-samples request over every dataset of a mesh engine fans
    out to the owners, and its selected call/allele counts and
    sample-hit unions equal the host-plane materialisation of each
    dataset (count planes present: restricted counting comes from the
    planes)."""
    from sbeacon_tpu.engine import host_match_rows, materialize_response
    from sbeacon_tpu.ops.kernel import QuerySpec
    from sbeacon_tpu.telemetry import flight_recorder

    names = [f"S{i}" for i in range(7)]
    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False))
    )
    shards = []
    for d in range(5):
        rng = random.Random(700 + d)
        recs = random_records(
            rng,
            chrom="7",
            n=250,
            n_samples=len(names),
            p_no_acan=0.5 if d % 2 else 0.0,
        )
        shards.append(
            build_index(
                recs,
                dataset_id=f"p{d}",
                vcf_location=f"v{d}",
                sample_names=names,
            )
        )
        eng.add_index(shards[-1])
    assert all(s.has_count_planes for s in shards)
    owners = {
        p.device for _k, _s, p in eng.index_snapshot() if p is not None
    }
    assert len(owners) == 5, "five planed datasets, five owner chips"
    selected = [0, 2, 6]
    fallbacks = sum(flight_recorder.fallbacks_by_site().values())
    rng = random.Random(99)
    pos0 = shards[0].cols["pos"]
    for _ in range(12):
        p = int(pos0[rng.randrange(len(pos0))])
        spec = QuerySpec(
            "7", max(1, p - 150), p + 150, 1, 1 << 30,
            alternate_bases="N",
        )
        pay = _selected_payload(
            [f"p{d}" for d in range(5)], spec, [names[i] for i in selected]
        )
        got = {r.dataset_id: r for r in eng.search(pay)}
        for di, shard in enumerate(shards):
            rows = host_match_rows(shard, spec, ref_wildcard=True)
            want = materialize_response(
                shard,
                rows,
                pay,
                chrom_label="7",
                dataset_id=f"p{di}",
                selected_idx=selected,
            )
            resp = got[f"p{di}"]
            assert resp.exists == want.exists
            assert resp.call_count == want.call_count
            assert resp.all_alleles_count == want.all_alleles_count
            assert resp.sample_indices == want.sample_indices
    assert eng.mesh_searches == 0, "plane readers fan out to the owners"
    assert sum(flight_recorder.fallbacks_by_site().values()) == fallbacks


def test_selected_or_sel_edges_on_the_owner():
    """Regression (r4 review), on the one resident copy: (a) a query
    whose only matches are the dataset's FIRST record must still report
    sample hits (padding lanes alias rec_id[0]); (b) an INFO row with
    ac=0 but set gt bits in a record BEFORE the first hit must stay
    excluded from the sample union (the grp >= k0 contract)."""
    from sbeacon_tpu.engine import host_match_rows, materialize_response
    from sbeacon_tpu.genomics.vcf import VcfRecord
    from sbeacon_tpu.ops.kernel import QuerySpec

    names = ["S0", "S1", "S2"]
    # record 1 (first in the shard): a real hit for S1
    # record 2: ac=0 but S2 carries the alt (INFO-sourced inconsistency)
    # record 3: the hit a later query finds (S0)
    recs = [
        VcfRecord("1", 100, "A", ["T"], ac=[2], an=6, vt="SNP",
                  genotypes=["0|0", "1|1", "0|0"]),
        VcfRecord("1", 200, "C", ["G"], ac=[0], an=6, vt="SNP",
                  genotypes=["0|0", "0|0", "0|1"]),
        VcfRecord("1", 300, "G", ["A"], ac=[1], an=6, vt="SNP",
                  genotypes=["1|0", "0|0", "0|0"]),
    ]
    shard = build_index(
        recs, dataset_id="edge", vcf_location="v", sample_names=names
    )
    eng = VariantEngine(
        BeaconConfig(engine=EngineConfig(microbatch=False))
    )
    eng.add_index(shard)
    assert eng.index_snapshot()[0][2] is not None, "planes on the owner"
    selected = [0, 1, 2]
    specs = [
        # (a) matches ONLY the first record
        QuerySpec("1", 100, 100, 1, 1 << 30, alternate_bases="N"),
        # (b) window covers the ac=0 record then the rec-3 hit
        QuerySpec("1", 150, 350, 1, 1 << 30, alternate_bases="N"),
    ]
    hits = []
    for spec in specs:
        pay = _selected_payload(["edge"], spec, names)
        (resp,) = eng.search(pay)
        rows = host_match_rows(shard, spec, ref_wildcard=True)
        want = materialize_response(
            shard, rows, pay, chrom_label="1", dataset_id="edge",
            selected_idx=selected,
        )
        assert resp.sample_indices == want.sample_indices, (spec, resp)
        assert resp.call_count == want.call_count, spec
        hits.append(resp.sample_indices)
    # (a) must see S1's hit; (b) must NOT include S2 (ac=0 record is
    # before k0) but must include S0
    assert hits[0] == [1], "first-record-only query lost its sample hits"
    assert hits[1] == [0]


def _genotype_derived_engines(n_ds=4, seed0=900):
    """Engines over genotype-derived corpora (restricted counting must
    come from the planes, incl. ploidy>2 overflow side tables)."""
    out = []
    for use_mesh in (True, False):
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(microbatch=False, use_mesh=use_mesh)
            )
        )
        names = [f"S{i}" for i in range(7)]
        for d in range(n_ds):
            rng = random.Random(seed0 + d)
            recs = random_records(
                rng,
                chrom="7",
                n=250,
                n_samples=len(names),
                p_multiallelic=0.3,
                p_no_acan=0.6,
            )
            for rec in recs[::9]:
                rec.genotypes[rng.randrange(len(names))] = "1|1|1"
                rec.ac = None
                rec.an = None
            eng.add_index(
                build_index(
                    recs,
                    dataset_id=f"d{d}",
                    vcf_location=f"v{d}.vcf.gz",
                    sample_names=names,
                )
            )
        out.append(eng)
    return out


def test_mesh_engine_serves_selected_samples_from_the_owners():
    """A multi-dataset selected-samples query through a mesh engine
    reads the one resident copy of the planes, on their owner chips
    (no mesh launch, no second copy in the stack), and returns
    oracle-equal per-dataset sample hits."""
    em, et = _genotype_derived_engines()
    for gran in ("record", "count", "boolean"):
        for details in (True, False):
            pay = _payload(
                selected_samples_only=True,
                sample_names={f"d{d}": ["S0", "S3", "S6"] for d in range(4)},
                include_samples=True,
                requested_granularity=gran,
                include_datasets="HIT" if details else "NONE",
            )
            rm, rt = em.search(pay), et.search(pay)
            assert em.mesh_searches == 0
            _assert_same(rm, rt)
    # narrow-window selected queries (per-record loop oracle)
    from sbeacon_tpu.engine import host_match_rows, materialize_response_loop
    from sbeacon_tpu.ops.kernel import QuerySpec

    shard0 = em._indexes[("d0", "v0.vcf.gz")][0]
    rng = random.Random(5)
    pos = shard0.cols["pos"]
    checked = 0
    for _ in range(6):
        p = int(pos[rng.randrange(shard0.n_rows)])
        pay = _payload(
            start_min=max(1, p - 200),
            start_max=p + 200,
            selected_samples_only=True,
            sample_names={f"d{d}": ["S1", "S4"] for d in range(4)},
            include_samples=True,
        )
        rm = em.search(pay)
        for resp in rm:
            ds = resp.dataset_id
            shard = em._indexes[(ds, f"{ds.replace('d', 'v')}.vcf.gz")][0]
            sel = [1, 4]
            spec = QuerySpec(
                "7", pay.start_min, pay.start_max, 1, 1 << 30,
                alternate_bases="N",
            )
            rows = host_match_rows(shard, spec, ref_wildcard=True)
            want = materialize_response_loop(
                shard, rows, pay, chrom_label="7", dataset_id=ds,
                selected_idx=sel,
            )
            assert resp.exists == want.exists
            assert resp.call_count == want.call_count
            assert resp.all_alleles_count == want.all_alleles_count
            assert resp.sample_indices == want.sample_indices
            checked += 1
    assert checked


def test_mesh_selected_heterogeneous_sample_widths():
    """Shards with DIFFERENT sample counts (plane widths) are each
    served from their own planes on their owner, in their own width,
    by a mesh engine as by a scatter engine."""
    out = []
    widths = [3, 40, 70]  # 1, 2, 3 plane words
    for use_mesh in (True, False):
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(microbatch=False, use_mesh=use_mesh)
            )
        )
        for d, n_samples in enumerate(widths):
            rng = random.Random(700 + d)
            names = [f"S{i}" for i in range(n_samples)]
            recs = random_records(
                rng, chrom="7", n=200, n_samples=n_samples,
                p_no_acan=0.5,
            )
            eng.add_index(
                build_index(
                    recs,
                    dataset_id=f"d{d}",
                    vcf_location=f"v{d}.vcf.gz",
                    sample_names=names,
                )
            )
        out.append(eng)
    em, et = out
    pay = _payload(
        selected_samples_only=True,
        sample_names={
            f"d{d}": [f"S{i}" for i in range(0, w, max(1, w // 4))]
            for d, w in enumerate(widths)
        },
        include_samples=True,
    )
    rm, rt = em.search(pay), et.search(pay)
    assert em.mesh_searches == 0
    widths_held = sorted(
        p.n_words for _k, _s, p in em.index_snapshot() if p is not None
    )
    assert widths_held == [1, 2, 3]
    _assert_same(rm, rt)
