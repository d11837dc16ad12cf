"""What a metadata version resolves to, kept until the version moves.

A variant query's filters and assembly resolve to dataset documents and
VCF sample names (``api/variants.py`` ``resolve_datasets``). The answer
depends on nothing in the request but ``(assemblyId, filters, dataset
ids)`` and on nothing in the process but the metadata and ontology
tables, which change only at ``/submit``, ``delete``,
``rebuild_indexes`` and an ontology registration; computing it steps
one sqlite row a dataset, and Python's sqlite3 gives the interpreter
lock up around every step (PERF.md 6, PR 25 and PR 38). So each store
carries a *generation* that moves with every commit to its tables, and
:class:`ResolveMemo` keeps answers for one generation.

**The one rule.** A writer bumps the generation AFTER its commit, inside
its write lock; a reader reads the generation FIRST, computes, and
stores the entry under the generation it read first. An entry is served
only to a reader that has just read that same generation: generations
only move forward, so no commit lies between the entry's computation
and that read. (Bumped before the commit, a reader could see the new
generation, read the old snapshot and keep it for ever.)

For a file-backed store the generation also has to move when ANOTHER
connection commits to the file (a second store on the same path, an
operator's tool): :class:`CommitClock`.
"""

from __future__ import annotations

import mmap
import sqlite3
import threading
from collections import OrderedDict

#: the WAL index (``<db>-shm``) starts with two copies of its 48-byte
#: header (https://www.sqlite.org/walformat.html#the_wal_index_file_format):
#: ``iChange`` (counted up by every transaction), ``mxFrame``, the salts
#: and the last frame's checksum. A commit in WAL mode IS the rewrite of
#: both copies, and sqlite's own ``PRAGMA data_version`` moves exactly
#: when a connection finds them changed (``walIndexTryHdr``).
_WAL_INDEX_HEADERS = 96


class CommitClock:
    """A value that differs after ANY connection, of this process or
    another, committed to a sqlite file; equal values mean no commit.

    In WAL mode it is the WAL index's two header copies, read through a
    shared mapping of the ``-shm`` file sqlite itself maps: the bytes
    sqlite compares to decide ``PRAGMA data_version``, without a
    statement, so without giving the interpreter lock up (on the chip's
    machines one hand-over of the lock is 2-4 ms of wall, and the
    pragma is two or three). A torn read differs from both the header
    before and the header after and costs one recomputation. The
    store's own connection keeps the ``-shm`` file alive and in place
    for as long as the store is open. Where there is no WAL index to
    map (the journal mode was refused), the clock asks
    ``PRAGMA data_version`` on one connection of its own, whose values
    compare with each other, under a lock."""

    def __init__(self, conn: sqlite3.Connection, path: str):
        self._headers = None
        self._conn = None
        if conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal":
            # any read maps the WAL index and, where it is new, writes
            # its first header
            conn.execute("SELECT COUNT(*) FROM sqlite_master").fetchone()
            try:
                with open(path + "-shm", "rb") as f:
                    self._headers = mmap.mmap(
                        f.fileno(), _WAL_INDEX_HEADERS, access=mmap.ACCESS_READ
                    )
            except (OSError, ValueError):
                pass
        if self._headers is None:
            self._lock = threading.Lock()
            self._conn = sqlite3.connect(path, check_same_thread=False)
            self._conn.execute("PRAGMA busy_timeout=10000")

    def read(self):
        if self._headers is not None:
            return self._headers[:_WAL_INDEX_HEADERS]
        with self._lock:
            return self._conn.execute("PRAGMA data_version").fetchone()[0]

    def close(self) -> None:
        if self._headers is not None:
            self._headers.close()
        else:
            with self._lock:
                self._conn.close()


class Generation:
    """One store's generation: the commits of its own connection,
    counted by the store after each one inside its write lock, and for
    a file-backed store the file's :class:`CommitClock` beside them.
    ``:memory:`` stores have one connection and the count is enough."""

    def __init__(self, conn: sqlite3.Connection, path: str):
        self._commits = 0
        self._clock = CommitClock(conn, path) if path != ":memory:" else None

    def committed(self) -> None:
        self._commits += 1

    def read(self):
        if self._clock is None:
            return self._commits
        return self._commits, self._clock.read()

    def close(self) -> None:
        if self._clock is not None:
            self._clock.close()


#: entries a memo keeps, least recently used out first. Filters are the
#: client's, so the key space is unbounded and the bound is not an
#: option. Sized from an entry: an assembly's dataset documents (128
#: small dicts in the largest deployment the benchmark runs, some 100
#: KB) or the sample names a filter list selects (at most every sample
#: of every dataset: 2,504 names are some 160 KB), so a full memo stays
#: in the tens of megabytes; the benchmark's cells send at most 51 keys.
RESOLVE_MEMO_ENTRIES = 256


class KeptSamples(tuple):
    """One dataset's selected sample names as the memo keeps them: the
    tuple every request of one filter list is handed, with room for what
    a consumer has resolved FROM it (``resolved``: the engine keeps the
    names' positions and mask words there, by the shard that serves the
    dataset; ``engine.VariantEngine._selection``). So a resolved
    selection lives and dies with its entry: the memo's bound, its
    generation rule and its lock are the only ones. At biobank width
    (18,191 names of 454,787) the names are 145 kB an entry and a
    resolved selection 0.35 MB a shard more."""

    def __new__(cls, names=()):
        self = super().__new__(cls, names)
        self.resolved = {}
        return self


class ResolveMemo:
    """Answers of ``resolve_datasets`` for ONE generation of the stores,
    owned by a :class:`MetadataStore` and dying with it. A read under
    another generation than the memo's drops every entry first."""

    def __init__(self):
        self._lock = threading.Lock()
        self._generation = None
        self._entries: OrderedDict = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._invalidations = 0

    def through(self, generation, key, compute):
        """The answer kept under ``key``, or ``compute()``'s, kept for
        the next caller if the memo still stands at ``generation``, which
        the caller read BEFORE this call: a commit during ``compute``
        drops the answer, and the next request computes again."""
        with self._lock:
            if generation != self._generation:
                if self._entries:
                    self._invalidations += 1
                    self._entries.clear()
                self._generation = generation
            value = self._entries.get(key)
            if value is not None:
                self._entries.move_to_end(key)
                self._hits += 1
                return value
            self._misses += 1
        value = compute()
        with self._lock:
            if generation == self._generation:
                self._entries[key] = value
                self._entries.move_to_end(key)
                while len(self._entries) > RESOLVE_MEMO_ENTRIES:
                    self._entries.popitem(last=False)
        return value

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self._hits,
                "misses": self._misses,
                "invalidations": self._invalidations,
                "entries": len(self._entries),
            }


def register_memo_metrics(registry, supplier) -> None:
    """The four ``filters.memo_*`` series over ``supplier()``'s memo."""

    def field(name):
        return lambda: supplier().stats()[name]

    registry.counter(
        "filters.memo_hits",
        "lookups of a variant query's resolution answered from the memo",
        fn=field("hits"),
    )
    registry.counter(
        "filters.memo_misses",
        "lookups computed from the metadata tables",
        fn=field("misses"),
    )
    registry.counter(
        "filters.memo_invalidations",
        "generation changes of the metadata or ontology tables that "
        "dropped memo entries",
        fn=field("invalidations"),
    )
    registry.gauge(
        "filters.memo_entries",
        "answers the memo holds for the current generation",
        fn=field("entries"),
    )
