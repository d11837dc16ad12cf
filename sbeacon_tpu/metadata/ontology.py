"""Ontology term-closure store.

Re-homes the reference's three DynamoDB ontology tables (reference:
dynamodb.tf Ontologies/Anscestors/Descendants; models in shared_resources/
dynamodb/ontologies.py) into one sqlite store, and replaces the indexer's
network calls to EBI OLS / CSIRO Ontoserver (reference: lambda/indexer/
lambda_function.py:62-97,137-192) with a pluggable resolver:

- ``register_edges``: load (child, parent) is-a edges from any local source
  (an OBO/OWL-derived edge list, a bundled subset, tests) and compute the
  full transitive closure in both directions.
- ``resolver``: optional callable term -> set[ancestor terms] for deployers
  with network access; results are cached in the same tables so the closure
  is fetched at index time, never at query time (same contract as the
  reference's indexer).

Terms with no known closure behave as their own singleton family —
identical to the reference's DoesNotExist fallback
(filter_functions.py:_get_term_descendants).
"""

from __future__ import annotations

import json
import sqlite3
import threading
from collections import defaultdict
from pathlib import Path
from typing import Callable, Iterable

from ..utils.trace import stage
from .memo import Generation


class OntologyStore:
    def __init__(self, path: str | Path = ":memory:"):
        if path != ":memory:":
            Path(path).parent.mkdir(parents=True, exist_ok=True)
        # served from every request thread over ONE connection: a
        # sqlite connection is not safe to step from two threads at
        # once (a statement releases the interpreter lock while it
        # runs, and a second thread's statement on the same connection
        # then fails with "bad parameter or other API misuse"), so
        # every use of it holds this lock. A closure is one row (a
        # JSON list), so a read is one statement and one row stepped.
        self._lock = threading.Lock()
        self.conn = sqlite3.connect(str(path), check_same_thread=False)
        if str(path) != ":memory:":
            # WAL: a commit by any connection shows in the WAL index,
            # which the generation reads without a statement
            self.conn.execute("PRAGMA journal_mode=WAL")
        self.conn.executescript(
            """
            CREATE TABLE IF NOT EXISTS ontologies (
                prefix TEXT PRIMARY KEY, data TEXT
            );
            CREATE TABLE IF NOT EXISTS ancestors (
                term TEXT PRIMARY KEY, terms TEXT
            );
            CREATE TABLE IF NOT EXISTS descendants (
                term TEXT PRIMARY KEY, terms TEXT
            );
            """
        )
        self.conn.commit()
        # what a closure read is valid for (metadata/memo.py has the
        # rule): every committing path bumps it after its commit
        self._generation = Generation(self.conn, str(path))
        self.resolver: Callable[[str], set[str]] | None = None

    def generation(self):
        """Moves with every commit to the ontology tables, from this
        store or, file-backed, from any other connection."""
        return self._generation.read()

    # -- ontology metadata (reference Ontologies table) ---------------------

    def put_ontology(self, prefix: str, data: dict) -> None:
        with self._lock:
            self.conn.execute(
                "INSERT OR REPLACE INTO ontologies VALUES (?, ?)",
                (prefix, json.dumps(data)),
            )
            self.conn.commit()
            self._generation.committed()

    def get_ontology(self, prefix: str) -> dict | None:
        with self._lock:
            row = self.conn.execute(
                "SELECT data FROM ontologies WHERE prefix = ?", (prefix,)
            ).fetchone()
        return json.loads(row[0]) if row else None

    def list_ontologies(self) -> list[dict]:
        with self._lock:
            rows = self.conn.execute(
                "SELECT data FROM ontologies ORDER BY prefix"
            ).fetchall()
        return [json.loads(r[0]) for r in rows]

    # -- closure ------------------------------------------------------------

    def register_edges(self, edges: Iterable[tuple[str, str]]) -> None:
        """(child, parent) is-a edges -> full bidirectional closure.

        Closures include the term itself (the reference stores ancestors
        including self: indexer records term->ancestors from the OLS
        hierarchicalAncestors + self).
        """
        parents: dict[str, set[str]] = defaultdict(set)
        terms: set[str] = set()
        for child, parent in edges:
            parents[child].add(parent)
            terms.add(child)
            terms.add(parent)

        anc: dict[str, set[str]] = {}

        def ancestors_of(t: str, stack: tuple = ()) -> set[str]:
            if t in anc:
                return anc[t]
            if t in stack:  # cycle guard
                return {t}
            out = {t}
            for p in parents.get(t, ()):
                out |= ancestors_of(p, stack + (t,))
            anc[t] = out
            return out

        for t in terms:
            ancestors_of(t)
        self._merge_closures(anc)

    def register_ancestors(self, term: str, ancestors: set[str]) -> None:
        """Directly record a term's ancestor set (resolver result shape)."""
        self._merge_closures({term: set(ancestors) | {term}})

    def _merge_closures(self, anc: dict[str, set[str]]) -> None:
        desc: dict[str, set[str]] = defaultdict(set)
        for t, ancs in anc.items():
            for a in ancs:
                desc[a].add(t)
        # one hold for the whole read-merge-write: two merges that
        # interleaved would each write back a closure without the other's
        with self._lock:
            cur = self.conn.cursor()
            for t, ancs in anc.items():
                ancs |= self._get_locked("ancestors", t) or set()
                cur.execute(
                    "INSERT OR REPLACE INTO ancestors VALUES (?, ?)",
                    (t, json.dumps(sorted(ancs))),
                )
            for t, descs in desc.items():
                descs |= self._get_locked("descendants", t) or set()
                cur.execute(
                    "INSERT OR REPLACE INTO descendants VALUES (?, ?)",
                    (t, json.dumps(sorted(descs))),
                )
            self.conn.commit()
            self._generation.committed()

    def _get_locked(self, table: str, term: str) -> set[str] | None:
        row = self.conn.execute(
            f"SELECT terms FROM {table} WHERE term = ?", (term,)
        ).fetchone()
        return set(json.loads(row[0])) if row else None

    def _get(self, table: str, term: str) -> set[str] | None:
        with self._lock:
            return self._get_locked(table, term)

    def get_ancestors(self, term: str) -> set[str] | None:
        return self._get("ancestors", term)

    def get_descendants(self, term: str) -> set[str] | None:
        return self._get("descendants", term)

    # -- expansion (the filter compiler's entry points) ---------------------

    def term_ancestors(self, term: str) -> set[str]:
        """Ancestors incl. self; unknown term -> {term}
        (reference _get_term_ancestors fallback)."""
        got = self.get_ancestors(term)
        if got is None and self.resolver is not None:
            try:
                fetched = self.resolver(term)
            except Exception:
                fetched = None
            if fetched is not None:
                self.register_ancestors(term, fetched)
                got = self.get_ancestors(term)
        return got if got is not None else {term}

    def term_descendants(self, term: str) -> set[str]:
        """Descendants incl. self; unknown term -> {term}."""
        with stage("filters.descendants"):
            got = self.get_descendants(term)
            return got if got is not None else {term}

    def expand_filter_term(
        self,
        term: str,
        *,
        include_descendants: bool = True,
        similarity: str = "high",
    ) -> set[str]:
        """Beacon similarity tiers (reference filter_functions.py:100-117):

        high   -> the term's own descendants;
        medium -> descendants of the ancestor half way up the closure;
        low    -> descendants of the broadest ancestor.
        """
        if not include_descendants:
            return {term}
        if similarity == "high":
            return self.term_descendants(term)
        ancestors = self.term_ancestors(term)
        families = sorted(
            (self.term_descendants(a) for a in ancestors), key=len
        )
        if similarity == "medium":
            return families[len(families) // 2]
        return families[-1]  # low

    def close(self) -> None:
        with self._lock:
            self._generation.close()
            self.conn.close()
