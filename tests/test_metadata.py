"""Metadata engine: entity store, relations, filter compiler, ontology."""

import pytest

from sbeacon_tpu.metadata import (
    MetadataStore,
    OntologyStore,
    entity_search_conditions,
    extract_terms,
)
from sbeacon_tpu.metadata.filters import FilterError


@pytest.fixture()
def onto():
    o = OntologyStore()
    # tiny is-a tree:   HP:1 -> HP:2 -> HP:4
    #                        \-> HP:3
    o.register_edges(
        [("HP:2", "HP:1"), ("HP:3", "HP:1"), ("HP:4", "HP:2")]
    )
    return o


@pytest.fixture()
def store(onto):
    s = MetadataStore(ontology=onto)
    s.upsert(
        "datasets",
        [
            {"id": "ds1", "assemblyId": "GRCh38", "name": "One",
             "vcfLocations": ["a.vcf.gz"]},
            {"id": "ds2", "assemblyId": "grch38", "name": "Two",
             "vcfLocations": ["b.vcf.gz"]},
            {"id": "ds3", "assemblyId": "GRCh37", "name": "Three"},
        ],
    )
    s.upsert(
        "individuals",
        [
            {"id": "i1", "datasetId": "ds1", "sex": {"id": "NCIT:C16576",
             "label": "female"}, "karyotypicSex": "XX",
             "diseases": [{"diseaseCode": {"id": "HP:4", "label": "leaf"}}]},
            {"id": "i2", "datasetId": "ds1", "sex": {"id": "NCIT:C20197",
             "label": "male"}, "karyotypicSex": "XY"},
            {"id": "i3", "datasetId": "ds2", "sex": {"id": "NCIT:C16576",
             "label": "female"}, "karyotypicSex": "XX",
             "diseases": [{"diseaseCode": {"id": "HP:3", "label": "mid"}}]},
        ],
    )
    s.upsert(
        "biosamples",
        [
            {"id": "b1", "datasetId": "ds1", "individualId": "i1",
             "sampleOriginType": {"id": "UBERON:0000178", "label": "blood"}},
            {"id": "b2", "datasetId": "ds2", "individualId": "i3",
             "sampleOriginType": {"id": "UBERON:0000955", "label": "brain"}},
        ],
    )
    s.upsert(
        "runs",
        [{"id": "r1", "datasetId": "ds1", "biosampleId": "b1",
          "individualId": "i1", "platform": "Illumina"}],
    )
    s.upsert(
        "analyses",
        [{"id": "a1", "datasetId": "ds1", "runId": "r1", "individualId": "i1",
          "biosampleId": "b1", "vcfSampleId": "S0001"}],
    )
    s.upsert("cohorts", [{"id": "c1", "name": "Cohort 1"}])
    s.rebuild_indexes()
    return s


def test_extract_terms_walks_nested_docs():
    doc = {
        "id": "i1",  # not CURIE-shaped -> skipped
        "sex": {"id": "NCIT:C16576", "label": "female"},
        "diseases": [
            {"diseaseCode": {"id": "HP:4", "label": "leaf"},
             "stage": {"id": "OGMS:0000119", "label": "acute"}}
        ],
    }
    terms = {t for t, _, _ in extract_terms(doc)}
    assert terms == {"NCIT:C16576", "HP:4", "OGMS:0000119"}


def test_fetch_count_exists_no_filters(store):
    assert store.count("individuals") == 3
    assert store.exists("datasets")
    docs = store.fetch("individuals", limit=2, skip=1)
    assert [d["id"] for d in docs] == ["i2", "i3"]


def test_own_column_filter(store):
    f = [{"id": "karyotypicSex", "operator": "=", "value": "XX"}]
    assert store.count("individuals", f) == 2
    f = [{"id": "karyotypicSex", "operator": "!", "value": "XX"}]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i2"]


def test_ontology_term_filter_descendant_expansion(store):
    # HP:2's descendants = {HP:2, HP:4}; only i1 carries HP:4
    f = [{"id": "HP:2"}]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i1"]
    # HP:1 expands to the whole family incl HP:3 (i3)
    f = [{"id": "HP:1"}]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i1", "i3"]
    # no descendant expansion: HP:2 itself is on nobody
    f = [{"id": "HP:2", "includeDescendantTerms": False}]
    assert store.count("individuals", f) == 0


def test_similarity_tiers(store, onto):
    # low similarity from HP:4 walks up to HP:1's family -> hits i1 and i3
    f = [{"id": "HP:4", "similarity": "low"}]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i1", "i3"]


def test_cross_entity_scope_filter(store):
    # individuals constrained by a biosample-scoped term
    f = [{"id": "UBERON:0000178", "scope": "biosamples"}]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i1"]


def test_linked_class_column_filter(store):
    # datasets filtered by a linked Individual column
    f = [{"id": "Individual.karyotypicSex", "operator": "=", "value": "XY"}]
    assert [d["id"] for d in store.fetch("datasets", f)] == ["ds1"]


def test_filter_intersection(store):
    f = [
        {"id": "NCIT:C16576"},  # female: i1, i3
        {"id": "HP:1"},  # disease family: i1, i3
        {"id": "karyotypicSex", "operator": "=", "value": "XX"},
    ]
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i1", "i3"]
    f.append({"id": "UBERON:0000955", "scope": "biosamples"})  # brain: i3
    assert [d["id"] for d in store.fetch("individuals", f)] == ["i3"]


def test_assembly_dataset_lookup_case_insensitive(store):
    ds = store.datasets_for_assembly("GRCh38")
    assert {d["id"] for d in ds} == {"ds1", "ds2"}
    ds = store.datasets_for_assembly("GRCh38", dataset_ids=["ds2"])
    assert [d["id"] for d in ds] == ["ds2"]


def test_filtering_terms_pagination(store):
    terms = store.filtering_terms(limit=100)
    ids = [t["id"] for t in terms]
    assert "NCIT:C16576" in ids and "UBERON:0000178" in ids
    assert ids == sorted(ids)
    page = store.filtering_terms(limit=2, skip=1)
    assert len(page) == 2 and page[0]["id"] == ids[1]


def test_sample_names_for_individual(store):
    assert store.sample_names_for_individual("i1") == {"ds1": ["S0001"]}
    assert store.sample_names_for_individual("i2") == {}


def test_relations_survive_missing_links(store):
    # ds3 has no individuals but must still appear in relations
    rows = store.query(
        "SELECT COUNT(*) FROM relations WHERE datasetid = 'ds3'"
    )
    assert rows[0][0] == 1


def test_upsert_replaces_and_reindexes(store):
    store.upsert(
        "individuals",
        [{"id": "i2", "datasetId": "ds1", "karyotypicSex": "XX",
          "sex": {"id": "NCIT:C20197", "label": "male"}}],
    )
    f = [{"id": "karyotypicSex", "operator": "=", "value": "XX"}]
    assert store.count("individuals", f) == 3


def test_filter_errors():
    with pytest.raises(FilterError):
        entity_search_conditions([{"value": "x"}], "individuals", "individuals")
    with pytest.raises(FilterError):
        entity_search_conditions(
            [{"id": "karyotypicSex", "operator": ">", "value": "XX"}],
            "individuals",
            "individuals",
        )
    with pytest.raises(FilterError):
        entity_search_conditions([{"id": "x"}], "nonsense", "individuals")


def test_sql_injection_resistant(store):
    evil = [{"id": "karyotypicSex", "operator": "=",
             "value": "x'; DROP TABLE individuals; --"}]
    assert store.count("individuals", evil) == 0
    assert store.count("individuals") == 3
    evil2 = [{"id": "EVIL:'; DROP TABLE relations; --"}]
    assert store.count("individuals", evil2) == 0


def test_ontology_resolver_hook(onto):
    calls = []

    def resolver(term):
        calls.append(term)
        return {"MONDO:ROOT"}

    onto.resolver = resolver
    # unknown term -> resolver consulted, closure cached
    assert onto.term_ancestors("MONDO:5") == {"MONDO:5", "MONDO:ROOT"}
    assert onto.term_ancestors("MONDO:5") == {"MONDO:5", "MONDO:ROOT"}
    assert calls == ["MONDO:5"]
    # descendants updated from the registered ancestors
    assert "MONDO:5" in onto.term_descendants("MONDO:ROOT")


def test_numeric_filters_compare_numerically(store):
    # TEXT storage must not fall back to lexicographic compare
    store.upsert("cohorts", [
        {"id": "c2", "name": "Big", "cohortSize": 1000},
        {"id": "c3", "name": "Small", "cohortSize": 90},
    ])
    store.rebuild_indexes()
    f = [{"id": "cohortSize", "operator": "<", "value": 200}]
    assert [d["id"] for d in store.fetch("cohorts", f)] == ["c3"]
    f = [{"id": "cohortSize", "operator": ">=", "value": 200}]
    assert [d["id"] for d in store.fetch("cohorts", f)] == ["c2"]
    # numeric '!' means !=
    f = [{"id": "cohortSize", "operator": "!", "value": 90}]
    assert "c3" not in [d["id"] for d in store.fetch("cohorts", f)]


def test_wal_reads_see_committed_writes_across_threads(tmp_path):
    """File-backed stores use per-thread WAL read connections; a write
    committed on the main connection must be immediately visible to a
    fresh reader thread, and concurrent readers must not interfere."""
    import threading

    from sbeacon_tpu.metadata import MetadataStore

    store = MetadataStore(tmp_path / "m.sqlite")
    store.upsert("datasets", [{"id": "d1", "name": "first"}])
    seen = {}

    def reader(k):
        seen[k] = store.get_by_id("datasets", "d1")

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(v and v["name"] == "first" for v in seen.values())
    # a later write is visible to the SAME reader threads' connections
    store.upsert("datasets", [{"id": "d1", "name": "second"}])
    out = {}

    def reader2(k):
        out[k] = store.get_by_id("datasets", "d1")["name"]

    threads = [threading.Thread(target=reader2, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert set(out.values()) == {"second"}
    store.close()


def test_dense_fetch_walk_matches_generic_shape(tmp_path):
    """The dense single-term fetch fast path (correlated-EXISTS walk)
    must return exactly the generic id-IN-subquery page: same rows,
    order, skip/limit behaviour."""
    import random

    from sbeacon_tpu.harness.scale import (
        populate_metadata_bulk,
        seed_phenotype_closure,
    )
    from sbeacon_tpu.metadata import MetadataStore, OntologyStore

    ont = OntologyStore()
    store = MetadataStore(tmp_path / "m.sqlite", ontology=ont)
    seed_phenotype_closure(ont)
    populate_metadata_bulk(store, n_datasets=4, individuals_per=60)
    store.rebuild_indexes()

    dense = [{"id": "NCIT:C16576"}]  # ~half the individuals
    ontology_f = [{"id": "HP:0000118", "includeDescendantTerms": True}]
    for filters in (dense, ontology_f):
        assert store._dense_single_term(filters, "individuals") is not None
        fast = store.fetch("individuals", filters, skip=5, limit=17)
        # force the generic shape by bypassing the heuristic
        where, params = store._compile(filters, "individuals")
        rows = store._read(
            f"SELECT _doc FROM individuals {where} "
            f"ORDER BY id LIMIT ? OFFSET ?",
            [*params, 17, 5],
        )
        import json as _json

        want = [_json.loads(r[0]) for r in rows]
        assert fast == want
    # sparse filters keep the generic path
    assert store._dense_single_term(
        [{"id": "HP:9999999", "includeDescendantTerms": False}], "individuals"
    ) is None
    # own-column and multi-filter shapes are never diverted
    assert store._dense_single_term(
        [{"id": "karyotypicSex", "operator": "=", "value": "XX"}],
        "individuals",
    ) is None
    assert store._dense_single_term(dense + ontology_f, "individuals") is None


def test_count_fast_path_matches_generic():
    """The term_counts fast path (r4: precomputed cardinalities +
    covering-index COUNT DISTINCT) must agree with the generic
    id-IN-subquery count on randomized corpora, for singleton and
    descendant-expanded filters, before and after re-upserts."""
    import random

    from sbeacon_tpu.metadata.ontology import OntologyStore
    from sbeacon_tpu.metadata.store import MetadataStore

    rng = random.Random(71)
    onto = OntologyStore()
    # HP:10 -> {HP:20, HP:30}; HP:20 -> {HP:21, HP:22}
    onto.register_edges(
        [("HP:20", "HP:10"), ("HP:30", "HP:10"),
         ("HP:21", "HP:20"), ("HP:22", "HP:20")]
    )
    store = MetadataStore(ontology=onto)
    terms = ["HP:20", "HP:30", "HP:21", "HP:22", "HP:99"]
    store.upsert("datasets", [{"id": "d0", "name": "d"}])
    docs = []
    for i in range(400):
        t = rng.choice(terms)
        docs.append(
            {
                "id": f"i{i}",
                "datasetId": "d0",
                "sex": {"id": t, "label": t},
            }
        )
    store.upsert("individuals", docs)
    store.rebuild_indexes()

    def generic_count(kind, filters):
        where, params = store._compile(filters, kind)
        return int(
            store._read(f"SELECT COUNT(*) FROM {kind} {where}", params)[0][0]
        )

    nonzero = 0
    for fid in ["HP:20", "HP:30", "HP:10", "HP:99", "HP:21", "HP:77"]:
        for desc in (True, False):
            filters = [{"id": fid, "includeDescendantTerms": desc}]
            fast = store.count("individuals", filters)
            want = generic_count("individuals", filters)
            assert fast == want, (fid, desc, fast, want)
            nonzero += fast > 0
    assert nonzero >= 6  # the battery must actually exercise hits

    # stale-consistency: upserts leave term_counts AND terms_index
    # equally stale — the two paths must still agree
    store.upsert(
        "individuals",
        [{"id": "extra", "datasetId": "d0", "sex": {"id": "HP:30"}}],
    )
    for fid in ["HP:30", "HP:10"]:
        filters = [{"id": fid}]
        assert store.count("individuals", filters) == generic_count(
            "individuals", filters
        ), fid
    # after rebuild the new row is visible through both
    store.rebuild_indexes()
    filters = [{"id": "HP:30"}]
    got = store.count("individuals", filters)
    assert got == generic_count("individuals", filters)
    assert got > 0

    # non-high similarity tiers bypass the precompute (plan-B fallback)
    for sim in ("medium", "low"):
        filters = [{"id": "HP:21", "similarity": sim}]
        assert store.count("individuals", filters) == generic_count(
            "individuals", filters
        ), sim
    # unknown term: zero through both paths
    filters = [{"id": "HP:404404"}]
    assert store.count("individuals", filters) == generic_count(
        "individuals", filters
    ) == 0


def test_count_fast_path_respects_deletes():
    """delete() must immediately disable the precomputed-cardinality
    lookup (the generic plan excludes deleted entities at once; the
    cached numbers cannot) and the fallback plan must agree with the
    generic count."""
    import random

    from sbeacon_tpu.metadata.ontology import OntologyStore
    from sbeacon_tpu.metadata.store import MetadataStore

    rng = random.Random(73)
    onto = OntologyStore()
    onto.register_edges([("HP:20", "HP:10"), ("HP:21", "HP:20")])
    store = MetadataStore(ontology=onto)
    store.upsert("datasets", [{"id": "d0", "name": "d"}])
    store.upsert(
        "individuals",
        [
            {
                "id": f"i{k}",
                "datasetId": "d0",
                "sex": {"id": rng.choice(["HP:20", "HP:21"]), "label": "x"},
            }
            for k in range(100)
        ],
    )
    store.rebuild_indexes()

    def generic(filters):
        where, params = store._compile(filters, "individuals")
        return int(
            store._read(
                f"SELECT COUNT(*) FROM individuals {where}", params
            )[0][0]
        )

    before = store.count("individuals", [{"id": "HP:10"}])
    assert before == generic([{"id": "HP:10"}]) == 100
    store.delete("individuals", "i7")
    for fid in ["HP:10", "HP:20", "HP:21"]:
        filters = [{"id": fid}]
        got = store.count("individuals", filters)
        assert got == generic(filters), (fid, got)
    # rebuild restores the O(1) lookup
    store.rebuild_indexes()
    assert store.count("individuals", [{"id": "HP:10"}]) == 99


def test_cross_entity_record_page_is_index_backed(store):
    """The /datasets/{id}/individuals record page must run as an index
    range walk, not a 1M-row scan-and-sort (VERDICT r4 next #6).
    Pins both the plan and the results."""
    store.upsert(
        "datasets", [{"id": f"ds{d}", "name": f"D{d}"} for d in range(3)]
    )
    store.upsert(
        "individuals",
        [
            {"id": f"i{k:03d}", "_datasetId": f"ds{k % 3}"}
            for k in range(90)
        ],
    )
    store.rebuild_indexes()
    plan = " ".join(
        r[-1]
        for r in store.query(
            "EXPLAIN QUERY PLAN SELECT _doc FROM individuals "
            "WHERE _datasetid = ? ORDER BY id LIMIT 10 OFFSET 0",
            ["ds1"],
        )
    )
    assert "individuals_dataset_id" in plan, plan
    assert "TEMP B-TREE" not in plan, plan  # ORDER BY rides the index
    docs = store.fetch(
        "individuals", [], extra_where="_datasetid = ?",
        extra_params=["ds1"], limit=10,
    )
    assert [d["id"] for d in docs] == [
        f"i{k:03d}" for k in range(90) if k % 3 == 1
    ][:10]


def test_variant_filters_resolve_to_samples_one_row_per_dataset(store):
    """A variant query's filters resolve to each dataset's VCF sample
    ids; the store answers with one row per dataset however many
    samples it selects, and analyses without a sample id select none."""
    from sbeacon_tpu.api.variants import resolve_datasets

    store.upsert(
        "biosamples",
        [{"id": "b3", "datasetId": "ds1", "individualId": "i2"}],
    )
    store.upsert(
        "runs",
        [{"id": "r2", "datasetId": "ds2", "biosampleId": "b2",
          "individualId": "i3"},
         {"id": "r3", "datasetId": "ds1", "biosampleId": "b3",
          "individualId": "i2"}],
    )
    store.upsert(
        "analyses",
        [
            {"id": f"a{k}", "datasetId": ds, "individualId": ind,
             "biosampleId": bio, "runId": run, "vcfSampleId": sample}
            for k, (ds, ind, bio, run, sample) in enumerate(
                [("ds1", "i1", "b1", "r1", "S0002"),
                 ("ds1", "i1", "b1", "r1", ""),
                 ("ds1", "i1", "b1", "r1", None),
                 ("ds2", "i3", "b2", "r2", "T0001"),
                 ("ds2", "i3", "b2", "r2", "T0002"),
                 ("ds1", "i2", "b3", "r3", "S0009")],  # male: not selected
                start=2,
            )
        ],
    )
    store.rebuild_indexes()
    asked = []
    read = store.query
    store.query = lambda sql, params=(): asked.append(read(sql, params)) or asked[-1]
    datasets, samples = resolve_datasets(
        store, None, "GRCh38",
        [{"id": "NCIT:C16576", "scope": "individuals"}],
    )
    assert [len(rows) for rows in asked] == [2]
    assert {ds: sorted(names) for ds, names in samples.items()} == {
        "ds1": ["S0001", "S0002"],
        "ds2": ["T0001", "T0002"],
    }
    assert sorted(d["id"] for d in datasets) == ["ds1", "ds2"]
    # a dataset filter is applied to the resolved ids
    datasets, _ = resolve_datasets(
        store, None, "GRCh38",
        [{"id": "NCIT:C16576", "scope": "individuals"}],
        dataset_ids=["ds2"],
    )
    assert [d["id"] for d in datasets] == ["ds2"]
    # no filters: the plain assembly scan, no sample selection
    datasets, samples = resolve_datasets(store, None, "GRCh38", [])
    assert sorted(d["id"] for d in datasets) == ["ds1", "ds2"]
    assert samples == {}
