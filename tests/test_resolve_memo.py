"""A metadata version is resolved once (ISSUE 38).

``resolve_datasets`` answers from the store's memo for as long as the
generations of the metadata and ontology stores stand still, and the
answer stays EXACT: a write that has returned is seen by the next
resolve, from this store or from another connection to the file.
"""

import json
import sqlite3
import sys
import threading

import pytest

from sbeacon_tpu.api.variants import resolve_datasets
from sbeacon_tpu.metadata import MetadataStore, OntologyStore
from sbeacon_tpu.metadata import memo as memo_mod
from sbeacon_tpu.metadata.memo import CommitClock, ResolveMemo
from sbeacon_tpu.telemetry import RequestContext, request_context
from sbeacon_tpu.utils.trace import tracer

FEMALE = [{"id": "NCIT:C16576", "scope": "individuals"}]
TREE = [{"id": "HP:1", "scope": "individuals"}]


def _analysis(k, ds, sample):
    return {"id": f"a{k}", "datasetId": ds, "individualId": f"i{k}",
            "biosampleId": f"b{k}", "runId": f"r{k}", "vcfSampleId": sample}


def _fill(store):
    """Two GRCh38 datasets of two analysed individuals each (one female
    with a leaf term of the HP tree, one male) and one GRCh37 dataset."""
    store.upsert("datasets", [
        {"id": "ds1", "assemblyId": "GRCh38", "name": "One"},
        {"id": "ds2", "assemblyId": "grch38", "name": "Two"},
        {"id": "ds3", "assemblyId": "GRCh37", "name": "Three"},
    ])
    people = [(1, "ds1", "NCIT:C16576", "HP:4"), (2, "ds1", "NCIT:C20197", None),
              (3, "ds2", "NCIT:C16576", "HP:3"), (4, "ds2", "NCIT:C20197", None)]
    store.upsert("individuals", [
        {"id": f"i{k}", "datasetId": ds, "sex": {"id": sex},
         **({"diseases": [{"diseaseCode": {"id": term}}]} if term else {})}
        for k, ds, sex, term in people
    ])
    store.upsert("biosamples", [
        {"id": f"b{k}", "datasetId": ds, "individualId": f"i{k}"}
        for k, ds, _sex, _term in people
    ])
    store.upsert("runs", [
        {"id": f"r{k}", "datasetId": ds, "individualId": f"i{k}",
         "biosampleId": f"b{k}"}
        for k, ds, _sex, _term in people
    ])
    store.upsert("analyses", [
        _analysis(k, ds, f"S{k}") for k, ds, _sex, _term in people
    ])
    store.rebuild_indexes()


def _edges(onto):
    # HP:1 -> HP:2 -> HP:4
    #      \-> HP:3
    onto.register_edges([("HP:2", "HP:1"), ("HP:3", "HP:1"), ("HP:4", "HP:2")])


@pytest.fixture(params=["memory", "file"])
def stores(request, tmp_path):
    """(store, ontology, open_another): in memory, or file-backed, where
    ``open_another()`` is a SECOND pair of stores on the same files."""
    opened = []

    def open_pair():
        if request.param == "memory":
            onto = OntologyStore()
            store = MetadataStore(ontology=onto)
        else:
            onto = OntologyStore(tmp_path / "ontology.sqlite")
            store = MetadataStore(tmp_path / "metadata.sqlite", ontology=onto)
        opened.append((store, onto))
        return store, onto

    store, onto = open_pair()
    _edges(onto)
    _fill(store)
    yield store, onto, (open_pair if request.param == "file" else None)
    for s, o in opened:
        s.close()
        o.close()


def _uncached(store, onto, *args, **kw):
    """The same resolve through an empty memo: computed from the tables."""
    kept, store.resolve_memo = store.resolve_memo, ResolveMemo()
    try:
        return resolve_datasets(store, onto, *args, **kw)
    finally:
        store.resolve_memo = kept


def _moved(store, before):
    after = store.resolve_memo.stats()
    return {k: after[k] - before[k] for k in ("hits", "misses", "invalidations")}


def test_a_hit_after_a_miss_is_the_uncached_answer(stores):
    store, onto, _ = stores
    for filters, ids in ((FEMALE, None), (TREE, None), ([], None),
                         (FEMALE, ["ds2"]), ([], ["ds1"])):
        before = store.resolve_memo.stats()
        first = resolve_datasets(store, onto, "GRCh38", filters, dataset_ids=ids)
        assert _moved(store, before)["misses"] >= 1
        before = store.resolve_memo.stats()
        again = resolve_datasets(store, onto, "grch38", filters, dataset_ids=ids)
        assert _moved(store, before) == {
            "hits": 2 if filters else 1, "misses": 0, "invalidations": 0,
        }
        assert first == again == _uncached(
            store, onto, "GRCh38", filters, dataset_ids=ids
        )
    datasets, samples = resolve_datasets(store, onto, "GRCh38", FEMALE)
    assert [d["id"] for d in datasets] == ["ds1", "ds2"]
    assert samples == {"ds1": ("S1",), "ds2": ("S3",)}
    assert resolve_datasets(store, onto, "GRCh38", TREE)[1] == samples
    # a filter list that selects nothing is an answer too, and is kept
    nobody = [{"id": "HP:9", "scope": "individuals"}]
    assert resolve_datasets(store, onto, "GRCh38", nobody) == ([], {})
    before = store.resolve_memo.stats()
    assert resolve_datasets(store, onto, "GRCh38", nobody) == ([], {})
    assert _moved(store, before) == {"hits": 1, "misses": 0, "invalidations": 0}


def test_the_filters_key_is_every_field_the_compiler_reads(stores):
    store, onto, _ = stores
    base = {"id": "HP:1", "scope": "individuals"}
    variants = [
        base,
        {**base, "includeDescendantTerms": False},
        {**base, "similarity": "low"},
        {**base, "scope": "biosamples"},
        {"id": "Individual.karyotypicSex", "operator": "=", "value": "XX"},
        {"id": "Individual.karyotypicSex", "operator": "!", "value": "XX"},
        {"id": "Individual.karyotypicSex", "operator": "=", "value": 1},
        {"id": "Individual.karyotypicSex", "operator": "=", "value": True},
        {"id": "Individual.karyotypicSex", "operator": "=", "value": "1"},
    ]
    before = store.resolve_memo.stats()
    answers = [resolve_datasets(store, onto, "GRCh38", [f]) for f in variants]
    # each is a key of its own (they may share the documents' entry)
    assert _moved(store, before)["misses"] >= len(variants)
    for f, answer in zip(variants, answers):
        assert resolve_datasets(store, onto, "GRCh38", [f]) == answer
        assert answer == _uncached(store, onto, "GRCh38", [f])
    # a field the compiler never reads is no part of the key
    before = store.resolve_memo.stats()
    resolve_datasets(store, onto, "GRCh38", [{**base, "label": "anything"}])
    assert _moved(store, before)["misses"] == 0


WRITES = {
    "upsert_datasets": lambda s, o: s.upsert(
        "datasets", [{"id": "ds4", "assemblyId": "GRCh38", "name": "Four"}]),
    "upsert_individuals": lambda s, o: s.upsert(
        "individuals", [{"id": "i9", "datasetId": "ds1",
                         "sex": {"id": "NCIT:C16576"}}]),
    "upsert_analyses": lambda s, o: s.upsert(
        "analyses", [_analysis(1, "ds1", "S1-renamed")]),
    "delete": lambda s, o: s.delete("datasets", "ds2"),
    "rebuild_indexes": lambda s, o: s.rebuild_indexes(),
    "register_edges": lambda s, o: o.register_edges([("HP:5", "HP:1")]),
    "register_ancestors": lambda s, o: o.register_ancestors("HP:6", {"HP:1"}),
    "put_ontology": lambda s, o: o.put_ontology("HP", {"id": "HP"}),
}


@pytest.mark.parametrize("write", sorted(WRITES))
def test_a_write_invalidates(stores, write):
    store, onto, _ = stores
    asked = [(FEMALE, None), (TREE, None), ([], None), ([], ["ds2"])]
    for filters, ids in asked * 2:
        resolve_datasets(store, onto, "GRCh38", filters, dataset_ids=ids)
    assert store.resolve_memo.stats()["entries"] >= 4
    generation = (store.generation(), onto.generation())
    before = store.resolve_memo.stats()
    WRITES[write](store, onto)
    assert (store.generation(), onto.generation()) != generation
    for n, (filters, ids) in enumerate(asked):
        answer = resolve_datasets(store, onto, "GRCh38", filters, dataset_ids=ids)
        assert answer == _uncached(store, onto, "GRCh38", filters, dataset_ids=ids)
        if n == 0:
            # the first lookup after the write dropped every entry
            assert _moved(store, before) == {
                "hits": 0, "misses": 2, "invalidations": 1,
            }
    assert _moved(store, before)["invalidations"] == 1
    if write == "upsert_analyses":
        assert resolve_datasets(store, onto, "GRCh38", FEMALE)[1]["ds1"] == (
            "S1-renamed",
        )
    if write == "delete":
        assert [d["id"] for d in resolve_datasets(store, onto, "GRCh38", [])[0]
                ] == ["ds1"]


def test_a_resolver_fetch_lands_under_the_older_generation(stores):
    """``term_ancestors`` may register what the external resolver
    fetched while a resolve computes: the entry is stored under the
    generation read first, is dropped, and the next request computes
    again: correct, once."""
    store, onto, _ = stores
    onto.resolver = lambda term: {"HP:1"}
    medium = [{"id": "HP:7", "scope": "individuals", "similarity": "medium"}]
    before = store.resolve_memo.stats()
    first = resolve_datasets(store, onto, "GRCh38", medium)
    second = resolve_datasets(store, onto, "GRCh38", medium)
    assert _moved(store, before)["hits"] == 0
    before = store.resolve_memo.stats()
    third = resolve_datasets(store, onto, "GRCh38", medium)
    assert _moved(store, before) == {"hits": 2, "misses": 0, "invalidations": 0}
    assert first == second == third == _uncached(store, onto, "GRCh38", medium)


@pytest.mark.parametrize("stores", ["file"], indirect=True)
def test_a_write_through_a_second_store_on_the_file_is_seen(stores):
    store, onto, open_another = stores
    for _ in range(2):
        resolve_datasets(store, onto, "GRCh38", FEMALE)
        resolve_datasets(store, onto, "GRCh38", TREE)
    other_store, other_onto = open_another()
    before = store.resolve_memo.stats()
    other_store.upsert("analyses", [_analysis(3, "ds2", "S3-elsewhere")])
    assert resolve_datasets(store, onto, "GRCh38", FEMALE)[1] == {
        "ds1": ("S1",), "ds2": ("S3-elsewhere",),
    }
    assert _moved(store, before)["invalidations"] == 1
    # ... and one to the ontology's file
    assert resolve_datasets(store, onto, "GRCh38", TREE)[1]["ds1"] == ("S1",)
    before = store.resolve_memo.stats()
    other_onto.register_edges([("NCIT:C20197", "HP:1")])  # the males, too
    samples = resolve_datasets(store, onto, "GRCh38", TREE)[1]
    assert {ds: sorted(names) for ds, names in samples.items()} == {
        "ds1": ["S1", "S2"], "ds2": ["S3-elsewhere", "S4"],
    }
    assert _moved(store, before)["invalidations"] == 1
    # a plain sqlite connection, as an operator's tool would open
    resolve_datasets(store, onto, "GRCh38", [])
    tool = sqlite3.connect(store._path)
    tool.execute("DELETE FROM datasets WHERE id = 'ds2'")
    tool.commit()
    tool.close()
    assert [d["id"] for d in resolve_datasets(store, onto, "GRCh38", [])[0]
            ] == ["ds1"]


@pytest.mark.parametrize("journal_mode", ["wal", "delete"])
def test_the_commit_clock_moves_with_any_connections_commit(tmp_path, journal_mode):
    """Equal readings mean no commit; in WAL mode the clock reads the
    mapped WAL index, elsewhere it asks ``PRAGMA data_version``."""
    path = str(tmp_path / "db.sqlite")
    own = sqlite3.connect(path)
    own.execute(f"PRAGMA journal_mode={journal_mode}")
    own.execute("CREATE TABLE t (v)")
    own.commit()
    clock = CommitClock(own, path)
    assert (clock._headers is not None) == (journal_mode == "wal")
    seen = [clock.read()]
    assert clock.read() == seen[0]
    other = sqlite3.connect(path)
    for conn in (own, other, own, other):
        conn.execute("INSERT INTO t VALUES (1)")
        assert clock.read() == seen[-1]  # not before the commit
        conn.commit()
        reading = clock.read()
        assert reading not in seen
        own.execute("SELECT COUNT(*) FROM t").fetchall()
        other.execute("SELECT COUNT(*) FROM t").fetchall()
        assert clock.read() == reading  # a read moves nothing
        seen.append(reading)
    clock.close()
    other.close()
    own.close()


@pytest.mark.parametrize(
    "stores, writer",
    [("memory", "same_store"), ("file", "same_store"), ("file", "second_store")],
    indirect=["stores"],
)
def test_read_your_writes_under_load(stores, writer):
    """Eight resolving threads, one writer: state ``k`` names dataset
    ds1's female sample ``W<k>``. A resolve that STARTED after
    ``upsert(k)`` returned never answers an older state, and every
    answer is a state some upsert had begun."""
    store, onto, open_another = stores
    write_store = open_another()[0] if writer == "second_store" else store
    states = 60
    started = committed = 0
    failures: list[str] = []
    done = threading.Event()

    def resolver():
        while not done.is_set() and not failures:
            low = committed
            samples = resolve_datasets(store, onto, "GRCh38", FEMALE)[1]
            high = started
            name = samples["ds1"][0]
            k = int(name[1:]) if name.startswith("W") else 0
            if not low <= k <= high or samples["ds2"] != ("S3",):
                failures.append(f"{samples} between states {low} and {high}")

    threads = [threading.Thread(target=resolver) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    for t in threads:
        t.start()
    try:
        for k in range(1, states + 1):
            started = k
            write_store.upsert("analyses", [_analysis(1, "ds1", f"W{k}")])
            committed = k
            if k % 10 == 0:
                # let the memo fill and serve between writes
                for _ in range(50):
                    resolve_datasets(store, onto, "GRCh38", FEMALE)
    finally:
        done.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures[:3]
    assert resolve_datasets(store, onto, "GRCh38", FEMALE)[1]["ds1"] == (
        f"W{states}",
    )
    stats = store.resolve_memo.stats()
    assert stats["invalidations"] >= states // 10 and stats["hits"] > 0


def test_a_caller_cannot_corrupt_a_later_hit(stores):
    """Outer containers are the caller's own; the documents are shared
    with later hits and refuse every change (copy first); the sample
    names are the kept tuples."""
    store, onto, _ = stores
    want = _uncached(store, onto, "GRCh38", FEMALE)
    changes = [
        lambda d: d.__setitem__("id", "mine"), lambda d: d.__delitem__("id"),
        lambda d: d.clear(), lambda d: d.pop("id"), lambda d: d.popitem(),
        lambda d: d.setdefault("x", 1), lambda d: d.update(id="mine"),
        lambda d: d.__ior__({"id": "mine"}),
    ]
    for _ in range(3):
        datasets, samples = resolve_datasets(store, onto, "GRCh38", FEMALE)
        assert (datasets, samples) == want
        for change in changes:
            with pytest.raises(TypeError, match="copy it"):
                change(datasets[0])
        mine = dict(datasets[0])
        mine["id"] = "mine"
        assert json.loads(json.dumps(datasets[1])) == want[0][1]
        datasets.pop()
        datasets.reverse()
        # a selection is the kept tuple itself (what the engine resolves
        # from it stays with it): the names cannot be changed
        assert isinstance(samples["ds1"], memo_mod.KeptSamples)
        assert isinstance(samples["ds1"], tuple)
        del samples["ds2"]
        samples["ds9"] = ["S9"]
    assert store.resolve_memo.stats()["hits"] >= 4
    # the unfiltered documents are one kept entry for every caller
    for _ in range(2):
        datasets, _none = resolve_datasets(store, onto, "GRCh38", [])
        assert datasets == want[0]
        datasets.clear()
    # ... and a resolve that bypasses the memo hands out the same kind
    datasets, _none = resolve_datasets(store, None, "GRCh38", FEMALE)
    with pytest.raises(TypeError):
        datasets[0]["id"] = "mine"


def test_the_bound_evicts_least_recently_used_first(stores, monkeypatch):
    store, onto, _ = stores
    monkeypatch.setattr(memo_mod, "RESOLVE_MEMO_ENTRIES", 6)
    kept = [{"id": "HP:1", "scope": "individuals"}]
    resolve_datasets(store, onto, "GRCh38", kept)
    for n in range(40):
        # a client's unbounded variety of filters: each is one key
        resolve_datasets(
            store, onto, "GRCh38", [{"id": f"HP:{100 + n}", "scope": "individuals"}]
        )
        resolve_datasets(store, onto, "GRCh38", kept)
        assert store.resolve_memo.stats()["entries"] <= 6
    before = store.resolve_memo.stats()
    assert resolve_datasets(store, onto, "GRCh38", kept) == _uncached(
        store, onto, "GRCh38", kept
    )
    assert _moved(store, before) == {"hits": 2, "misses": 0, "invalidations": 0}
    before = store.resolve_memo.stats()
    resolve_datasets(store, onto, "GRCh38", [{"id": "HP:100", "scope": "individuals"}])
    assert _moved(store, before)["hits"] == 0  # long gone


def test_another_ontology_than_the_stores_own_is_not_memoised(stores):
    """The memo's generation is its store's and its store's ontology's:
    a resolve against any other ontology computes as it always did."""
    store, onto, _ = stores
    other = OntologyStore()
    before = store.resolve_memo.stats()
    for _ in range(2):
        assert resolve_datasets(store, other, "GRCh38", TREE) == ([], {})
        assert resolve_datasets(store, None, "GRCh38", TREE) == ([], {})
    assert _moved(store, before) == {"hits": 0, "misses": 0, "invalidations": 0}
    assert resolve_datasets(store, onto, "GRCh38", TREE)[1] == {
        "ds1": ("S1",), "ds2": ("S3",),
    }
    other.close()


def test_a_hit_is_still_one_stage_sample_and_one_entry_of_the_request(stores):
    store, onto, _ = stores
    resolve_datasets(store, onto, "GRCh38", TREE)
    for filters in (TREE, []):
        resolve_datasets(store, onto, "GRCh38", filters)
        count, sum_ms, _req = tracer.stage_counts("filters.resolve")
        inner = tracer.stage_counts("filters.descendants")[0]
        before = store.resolve_memo.stats()
        ctx = RequestContext(route="g_variants")
        with request_context(ctx):
            resolve_datasets(store, onto, "GRCh38", filters)
        assert _moved(store, before)["misses"] == 0
        after = tracer.stage_counts("filters.resolve")
        assert after[0] == count + 1
        assert list(ctx.stages) == ["filters.resolve"]
        assert ctx.stages["filters.resolve"] == pytest.approx(after[1] - sum_ms)
        # the closure is not read again
        assert tracer.stage_counts("filters.descendants")[0] == inner


def test_row_counts_are_kept_for_one_generation(stores):
    """``_row_count`` (the density heuristic's COUNT(*)) follows the
    same rule: keyed by the generation read before the count, so no
    write, from any connection, can leave an older count standing."""
    store, onto, open_another = stores
    assert store._row_count("individuals") == 4
    reads = []
    read = store._read
    store._read = lambda sql, params=(): reads.append(sql) or read(sql, params)
    assert store._row_count("individuals") == 4
    assert reads == []
    store.upsert("individuals", [{"id": "i5", "datasetId": "ds1"}])
    assert store._row_count("individuals") == 5
    store.delete("individuals", "i5")
    assert store._row_count("individuals") == 4
    assert len(reads) == 2
    if open_another is not None:
        open_another()[0].upsert("individuals", [{"id": "i6", "datasetId": "ds2"}])
        assert store._row_count("individuals") == 5
    # a count that raced a commit is stored under the older generation
    generation = store.generation()
    store.upsert("individuals", [{"id": "i7", "datasetId": "ds2"}])
    store._kind_counts["individuals"] = (generation, 1)
    assert store._row_count("individuals") == (
        6 if open_another is not None else 5
    )


def test_the_memo_is_served_in_metrics_and_debug_status():
    from sbeacon_tpu.api import BeaconApp

    app = BeaconApp()
    try:
        app.store.upsert(
            "datasets", [{"id": "m1", "name": "m1", "_assemblyId": "GRCh38"}]
        )
        for _ in range(3):
            resolve_datasets(app.store, app.ontology, "GRCh38", [])
        want = {"hits": 2, "misses": 1, "invalidations": 0, "entries": 1}
        assert app.handle("GET", "/debug/status")[1]["filters"] == {"memo": want}
        served = app.handle("GET", "/metrics")[1]["filters"]
        assert served == {f"memo_{k}": v for k, v in want.items()}
        text = app.telemetry.render_prometheus()
        assert "sbeacon_filters_memo_hits 2" in text
        assert "# TYPE sbeacon_filters_memo_entries gauge" in text
        app.store.upsert(
            "datasets", [{"id": "m2", "name": "m2", "_assemblyId": "GRCh38"}]
        )
        assert len(resolve_datasets(app.store, app.ontology, "GRCh38", [])[0]) == 2
        assert app.handle("GET", "/metrics")[1]["filters"][
            "memo_invalidations"
        ] == 1
    finally:
        app.close()
