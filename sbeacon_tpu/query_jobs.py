"""Async variant-query job table: the VariantQuery state machine re-homed.

The reference tracks each distributed variant query in two DynamoDB tables
(reference: dynamodb.tf:100-149): ``VariantQueries`` — one row per query
with an atomic ``fanOut`` counter, start/end/elapsed times and a 5-minute
TTL (shared_resources/dynamodb/variant_queries.py:29-59) — and
``VariantQueryResponses`` — one row per worker result, spilling any body
over 300 KB to ``variant-queries/{uuid}.json`` in S3 with a 24-hour TTL
(performQuery/search_variants.py:282-300; s3.tf:22-28). Queries are keyed
by an md5 of the request (apiutils/request_hash.py:6-13) and a stubbed
``get_job_status`` (variant_queries.py:94-103 — always ``NEW``, "TODO
implement caching") decides whether to recompute.

Here the fan-out/fan-in apparatus is gone — one compiled program answers
the whole query (SURVEY.md §2.5) — but the *job* semantics remain useful
and are implemented for real rather than stubbed: request-hash keyed
jobs, RUNNING detection (concurrent identical queries coalesce), COMPLETE
result caching with TTL, spill-to-file for oversized response sets, and a
crash-surviving sqlite ledger (same pattern as ``ingest.ledger``).

What is written when, and by which thread. In one process everything a
request needs is in the runner's memory: ``_results`` (the finished jobs'
hand-offs, kept for the query TTL), ``_done`` (the jobs in flight: the
single-flight registry and the claim) and ``QueryJobTable.restored`` (the
completed rows a restart found, id -> expiry). A request reads those
under the runner's lock and runs NO sqlite statement unless its id is a
restored row. Nothing is written for a job that is merely RUNNING. A job
that finished whole is handed to its waiters, then queued for the
runner's ONE writer thread, which takes whatever is queued and writes it
in one transaction (``QueryJobTable.write_jobs``: the query row already
complete, its response rows, oversized bodies spilled first), commits
once and loops: group commit with no timer, so an idle server writes a
job at once and a busy one shares the commit. The minute's TTL purge and
WAL checkpoint run on that thread between batches. So the table is a
write-behind journal: it answers what memory cannot only after a restart
(completed jobs inside their TTL survive with their spills; a job in
flight at a crash left no row and reads NEW). The client was never told
its result was durable: the hand-off precedes the write, as it always
did. Degraded (partial) and failed results are never queued.

The step-by-step methods (``start``, ``next_response_number``,
``put_response``, ``mark_finished``, ``complete``, ``abandon``, ``wait``)
are the reference's state machine, row by row, and are kept as such; the
runner no longer calls them. The ``fan_out``/``responses`` counters are
kept per job for observability parity with the reference's table schema.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import queue
import sqlite3
import threading
import time
import uuid
from enum import Enum
from pathlib import Path
from typing import NamedTuple

from concurrent.futures import ThreadPoolExecutor

from .config import ResilienceConfig
from .harness.faults import fault_point
from .payloads import VariantSearchResponse, fields_of
from .resilience import (
    AdmissionController,
    Overloaded,
    current_deadline,
    deadline_scope,
)
from .telemetry import (
    annotate,
    current_context,
    request_context,
)
from .utils.trace import span, stage, tracer


class JobStatus(Enum):
    """reference: variant_queries.py:88-92 (EXPIRED is implicit there via
    the DynamoDB TTL delete; explicit here)."""

    COMPLETED = 1
    RUNNING = 2
    NEW = 3
    EXPIRED = 4


def hash_query(doc: dict | str) -> str:
    """Stable md5 of a request document — reference
    apiutils/request_hash.py:6-13 (sorted-key json of the event)."""
    if not isinstance(doc, str):
        doc = json.dumps(doc, sort_keys=True, default=str)
    return hashlib.md5(doc.encode()).hexdigest()


class FinishedJob(NamedTuple):
    """A job that finished whole, as the runner hands it to its writer:
    everything its rows hold (``QueryJobTable.write_jobs``)."""

    query_id: str
    responses: list
    start_time: float
    end_time: float
    expires_at: float


class _TableLock:
    """The job table's one lock, around its one connection. The writer
    thread holds it for a batch's transaction and for the sweep; a
    request takes it only to read a restored job's responses. A thread
    that finds it taken waits: that wait is the ``runner.table_wait``
    stage, timed only when the lock is contended. The sample is handed
    to the stage after the release, so nothing but the table's own work
    runs under the lock."""

    __slots__ = ("_lock", "_waited")

    def __init__(self):
        self._lock = threading.Lock()
        # thread id -> ms its current hold waited for the lock
        self._waited: dict[int, float] = {}

    def __enter__(self):
        if not self._lock.acquire(False):
            t0 = time.perf_counter()
            self._lock.acquire()
            self._waited[threading.get_ident()] = (
                time.perf_counter() - t0
            ) * 1e3

    def __exit__(self, *exc):
        self._lock.release()
        if self._waited:
            ms = self._waited.pop(threading.get_ident(), None)
            if ms is not None:
                tracer.observe("runner.table_wait", ms)
        return False


class QueryJobTable:
    """Sqlite-backed VariantQueries + VariantQueryResponses equivalent.

    Thread-safe within a process (one lock around the shared connection,
    matching ``ingest.ledger``); durable across restarts: ``restored``
    holds what this open found.
    """

    def __init__(
        self,
        path: str | Path = ":memory:",
        *,
        spill_dir: str | Path | None = None,
        query_ttl_s: float = 300.0,  # VariantQuery timeToExist: 5 min
        response_ttl_s: float = 24 * 3600.0,  # VariantQueryResponses: 24 h
        inline_limit: int = 300 * 1024,  # performQuery spill threshold
    ):
        self._conn = sqlite3.connect(str(path), check_same_thread=False)
        # WAL + NORMAL sync: commit cost drops from per-commit fsync to
        # WAL append — right durability trade for a TTL'd cache table (the
        # reference's DynamoDB was eventually consistent too); harmless
        # no-op for :memory:
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute("PRAGMA synchronous=NORMAL")
        # NO auto-checkpoint: whichever commit crosses the page
        # threshold absorbs the full checkpoint fsync — on the serving
        # thread that was a >1 s p99 outlier with warm kernels. The
        # runner's writer thread calls checkpoint() between batches
        # instead (WAL growth bounded by one sweep interval of TTL'd
        # cache traffic).
        self._conn.execute("PRAGMA wal_autocheckpoint=0")
        self._lock = _TableLock()
        self.spill_dir = Path(spill_dir) if spill_dir else None
        if self.spill_dir:
            self.spill_dir.mkdir(parents=True, exist_ok=True)
        self.query_ttl_s = query_ttl_s
        self.response_ttl_s = response_ttl_s
        self.inline_limit = inline_limit
        with self._lock:
            self._conn.executescript(
                """
                CREATE TABLE IF NOT EXISTS variant_queries (
                    id TEXT PRIMARY KEY,
                    claim TEXT NOT NULL,
                    complete INTEGER NOT NULL DEFAULT 0,
                    fan_out INTEGER NOT NULL DEFAULT 0,
                    responses INTEGER NOT NULL DEFAULT 0,
                    responses_counter INTEGER NOT NULL DEFAULT 0,
                    start_time REAL NOT NULL,
                    end_time REAL,
                    elapsed_time REAL NOT NULL DEFAULT -1,
                    expires_at REAL NOT NULL
                );
                CREATE TABLE IF NOT EXISTS variant_query_responses (
                    query_id TEXT NOT NULL,
                    response_number INTEGER NOT NULL,
                    body TEXT,
                    spill_path TEXT,
                    expires_at REAL NOT NULL,
                    PRIMARY KEY (query_id, response_number)
                );
                """
            )
            self._conn.commit()
        # crash recovery: incomplete rows are claims held by workers of a
        # dead process — no thread in this (or any new) process will ever
        # complete them, so identical queries would stall on RUNNING for
        # up to the full TTL. Drop them (and their partial responses) now;
        # the reference analogue is the TTL delete, just not lazily.
        with self._lock, self._conn:
            stale = [
                qid
                for (qid,) in self._conn.execute(
                    "SELECT id FROM variant_queries WHERE complete = 0"
                )
            ]
            spilled = []
            for qid in stale:
                spilled += self._conn.execute(
                    "SELECT spill_path FROM variant_query_responses"
                    " WHERE query_id = ? AND spill_path IS NOT NULL",
                    (qid,),
                ).fetchall()
                self._conn.execute(
                    "DELETE FROM variant_queries WHERE id = ?", (qid,)
                )
                self._conn.execute(
                    "DELETE FROM variant_query_responses WHERE query_id = ?",
                    (qid,),
                )
            #: the completed jobs that survived the restart and can
            #: still answer, id -> expires_at: read once, here. The
            #: runner consults it from memory (under its own lock) and
            #: drops entries as they expire; after ``query_ttl_s`` it is
            #: empty and stays so.
            self.restored: dict[str, float] = dict(
                self._conn.execute(
                    "SELECT id, expires_at FROM variant_queries"
                    " WHERE complete = 1 AND expires_at > ?",
                    (time.time(),),
                )
            )
        for (p,) in spilled:
            Path(p).unlink(missing_ok=True)

    # -- finished jobs, written whole ----------------------------------------

    def _spill(self, body: str) -> tuple[str | None, str | None]:
        """(body, spill_path) as a response row holds them: a body past
        ``inline_limit`` goes to a file of ``spill_dir`` — reference
        performQuery/search_variants.py:282-300."""
        if len(body) <= self.inline_limit or self.spill_dir is None:
            return body, None
        spill_path = str(self.spill_dir / f"{uuid.uuid4()}.json")
        Path(spill_path).write_text(body)
        return None, spill_path

    def write_jobs(self, jobs: list[FinishedJob]) -> None:
        """Write finished jobs in ONE transaction: per job the query row
        already complete and one row a response, oversized bodies
        spilled before the transaction opens. Rows an earlier run of the
        same id left behind (its response rows outlive the query row)
        are replaced and their spills unlinked. If the commit fails
        nothing of the batch is stored and its spills are removed."""
        now = time.time()
        query_rows, response_rows, spills = [], [], []
        for job in jobs:
            for n, resp in enumerate(job.responses, 1):
                body, spill_path = self._spill(resp.dumps())
                if spill_path:
                    spills.append(spill_path)
                response_rows.append(
                    (job.query_id, n, body, spill_path,
                     now + self.response_ttl_s)
                )
            n = len(job.responses)
            query_rows.append(
                (job.query_id, uuid.uuid4().hex, 1, 0, n, n, job.start_time,
                 job.end_time, job.end_time - job.start_time,
                 job.expires_at)
            )
        try:
            fault_point("sqlite.commit", "write_jobs")
            with self._lock, self._conn:
                stale = self._in_chunks(
                    "DELETE FROM variant_query_responses WHERE query_id IN"
                    " (VALUES {}) RETURNING spill_path",
                    [(job.query_id,) for job in jobs],
                )
                self._in_chunks(
                    "INSERT OR REPLACE INTO variant_queries"
                    " (id, claim, complete, fan_out, responses,"
                    " responses_counter, start_time, end_time,"
                    " elapsed_time, expires_at) VALUES {}",
                    query_rows,
                )
                self._in_chunks(
                    "INSERT INTO variant_query_responses"
                    " (query_id, response_number, body, spill_path,"
                    " expires_at) VALUES {}",
                    response_rows,
                )
        except BaseException:
            for p in spills:
                Path(p).unlink(missing_ok=True)
            raise
        for (p,) in stale:
            if p:
                Path(p).unlink(missing_ok=True)

    #: bound values a statement of ``_in_chunks`` carries at most (sqlite
    #: builds before 3.32 refuse more than 999)
    MAX_BOUND = 900

    def _in_chunks(self, sql: str, rows: list[tuple]) -> list:
        """Run ``sql`` once for as many ``rows`` as one statement can
        bind (``{}`` takes their placeholders, a row in parentheses), and
        return what the statements return.
        Not ``executemany``: sqlite3 steps that once a row, and gives the
        interpreter lock up for every step; under a serving load each
        such hand-over costs a thread's wake-up (PERF.md 6, PR 25), so a
        job of 32 responses is written in as many statements as a job of
        one."""
        out = []
        if not rows:
            return out
        width = len(rows[0])
        one = "(" + ",".join("?" * width) + ")"
        per = self.MAX_BOUND // width
        for i in range(0, len(rows), per):
            chunk = rows[i:i + per]
            out += self._conn.execute(
                sql.format(",".join([one] * len(chunk))),
                [value for row in chunk for value in row],
            ).fetchall()
        return out

    # -- job lifecycle, step by step (the reference's state machine) ---------

    def get_job_status(self, query_id: str) -> JobStatus:
        """The un-stubbed version of reference variant_queries.py:94-103."""
        now = time.time()
        with self._lock:
            row = self._conn.execute(
                "SELECT complete, expires_at FROM variant_queries"
                " WHERE id = ?",
                (query_id,),
            ).fetchone()
        if row is None:
            return JobStatus.NEW
        complete, expires_at = row
        if now >= expires_at:
            return JobStatus.EXPIRED
        return JobStatus.COMPLETED if complete else JobStatus.RUNNING

    def start(self, query_id: str, *, fan_out: int = 0) -> str | None:
        """Claim a query id for execution; returns an opaque claim token,
        or None when an unexpired job already holds the claim (the
        concurrent-identical-query coalescing the reference's stub never
        delivered). All subsequent writes require the token, so a worker
        whose claim was reclaimed after TTL expiry cannot corrupt the new
        owner's job (the reference's conditional-expression ownership,
        summariseSlice/main.cpp:367-368, re-expressed)."""
        now = time.time()
        claim = uuid.uuid4().hex
        with self._lock, self._conn:
            spilled = self._conn.execute(
                "SELECT r.spill_path FROM variant_query_responses r"
                " JOIN variant_queries q ON q.id = r.query_id"
                " WHERE q.id = ? AND q.expires_at <= ?"
                " AND r.spill_path IS NOT NULL",
                (query_id, now),
            ).fetchall()
            purged = self._conn.execute(
                "DELETE FROM variant_queries WHERE id = ? AND expires_at <= ?",
                (query_id, now),
            )
            if purged.rowcount:
                self._conn.execute(
                    "DELETE FROM variant_query_responses WHERE query_id = ?",
                    (query_id,),
                )
            try:
                self._conn.execute(
                    "INSERT INTO variant_queries"
                    " (id, claim, fan_out, start_time, expires_at)"
                    " VALUES (?,?,?,?,?)",
                    (query_id, claim, fan_out, now, now + self.query_ttl_s),
                )
            except sqlite3.IntegrityError:
                return None
        for (p,) in spilled:
            Path(p).unlink(missing_ok=True)
        return claim

    def _owns(self, query_id: str, claim: str) -> bool:
        row = self._conn.execute(
            "SELECT 1 FROM variant_queries WHERE id = ? AND claim = ?",
            (query_id, claim),
        ).fetchone()
        return row is not None

    def next_response_number(self, query_id: str, claim: str) -> int:
        """Atomic increment — reference VariantQuery.getResponseNumber
        (variant_queries.py:45-50). 0 when the claim has been lost."""
        with self._lock, self._conn:
            if not self._owns(query_id, claim):
                return 0
            self._conn.execute(
                "UPDATE variant_queries SET responses_counter ="
                " responses_counter + 1 WHERE id = ?",
                (query_id,),
            )
            (n,) = self._conn.execute(
                "SELECT responses_counter FROM variant_queries WHERE id = ?",
                (query_id,),
            ).fetchone()
        return int(n)

    def put_response(
        self,
        query_id: str,
        response_number: int,
        resp: VariantSearchResponse,
        claim: str,
    ) -> bool:
        """Store one worker response, spilling past ``inline_limit`` —
        reference performQuery/search_variants.py:282-300. Refused (False)
        when the claim is no longer held."""
        body, spill_path = self._spill(resp.dumps())
        fault_point("sqlite.commit", "put_response")
        now = time.time()
        with self._lock, self._conn:
            if not self._owns(query_id, claim):
                ok = False
            else:
                ok = True
                self._conn.execute(
                    "INSERT OR REPLACE INTO variant_query_responses"
                    " (query_id, response_number, body, spill_path,"
                    " expires_at) VALUES (?,?,?,?,?)",
                    (
                        query_id,
                        response_number,
                        body,
                        spill_path,
                        now + self.response_ttl_s,
                    ),
                )
        if not ok and spill_path:
            Path(spill_path).unlink(missing_ok=True)
        return ok

    def mark_finished(self, query_id: str, claim: str) -> int:
        """Atomic fan-in decrement; returns remaining fan_out — reference
        VariantQuery.markFinished (variant_queries.py:53-59)."""
        with self._lock, self._conn:
            if not self._owns(query_id, claim):
                return -1
            self._conn.execute(
                "UPDATE variant_queries SET responses = responses + 1,"
                " fan_out = fan_out - 1, end_time = ? WHERE id = ?",
                (time.time(), query_id),
            )
            (remaining,) = self._conn.execute(
                "SELECT fan_out FROM variant_queries WHERE id = ?",
                (query_id,),
            ).fetchone()
        return int(remaining)

    def complete(self, query_id: str, claim: str) -> bool:
        fault_point("sqlite.commit", "complete")
        now = time.time()
        with self._lock, self._conn:
            if not self._owns(query_id, claim):
                return False
            self._conn.execute(
                "UPDATE variant_queries SET complete = 1, end_time = ?,"
                " elapsed_time = ? - start_time WHERE id = ?",
                (now, now, query_id),
            )
        return True

    def abandon(self, query_id: str, claim: str) -> None:
        """Drop a failed job so its id reads NEW again — a crashed worker
        must not cache an empty result set as the answer (the reference's
        analogue: a lost slice simply stays pending and is re-run)."""
        with self._lock, self._conn:
            if not self._owns(query_id, claim):
                return
            spilled = self._conn.execute(
                "SELECT spill_path FROM variant_query_responses"
                " WHERE query_id = ? AND spill_path IS NOT NULL",
                (query_id,),
            ).fetchall()
            self._conn.execute(
                "DELETE FROM variant_queries WHERE id = ?", (query_id,)
            )
            self._conn.execute(
                "DELETE FROM variant_query_responses WHERE query_id = ?",
                (query_id,),
            )
        for (p,) in spilled:
            Path(p).unlink(missing_ok=True)

    def wait(self, query_id: str, timeout_s: float = 600.0) -> bool:
        """Poll fan_out==0 / complete — the reference's fan-in loop
        (variantutils/search_variants.py:130-141), REQUEST_TIMEOUT 600 s.
        Clamped by the caller's ambient request deadline: a 600 s poll
        budget never outlives the request it serves."""
        timeout_s = current_deadline().clamp(timeout_s)
        deadline = time.time() + timeout_s
        delay = 0.002
        while time.time() < deadline:
            status = self.get_job_status(query_id)
            if status is JobStatus.COMPLETED:
                return True
            if status in (JobStatus.NEW, JobStatus.EXPIRED):
                return False
            time.sleep(delay)
            delay = min(delay * 2, 0.1)
        return False

    # -- results -------------------------------------------------------------

    def get_responses(self, query_id: str) -> list[VariantSearchResponse]:
        """Rehydrate all responses (spilled bodies read back from disk) —
        reference search_variants.py:142-155 batch_get + S3 fetch."""
        with self._lock:
            rows = self._conn.execute(
                "SELECT body, spill_path FROM variant_query_responses"
                " WHERE query_id = ? ORDER BY response_number",
                (query_id,),
            ).fetchall()
        out = []
        for body, spill_path in rows:
            if body is None and spill_path:
                body = Path(spill_path).read_text()
            if body is not None:
                out.append(VariantSearchResponse.loads(body))
        return out

    def info(self, query_id: str) -> dict | None:
        with self._lock:
            row = self._conn.execute(
                "SELECT id, complete, fan_out, responses, responses_counter,"
                " start_time, end_time, elapsed_time, expires_at"
                " FROM variant_queries WHERE id = ?",
                (query_id,),
            ).fetchone()
        if row is None:
            return None
        keys = (
            "id",
            "complete",
            "fan_out",
            "responses",
            "responses_counter",
            "start_time",
            "end_time",
            "elapsed_time",
            "expires_at",
        )
        return dict(zip(keys, row))

    def purge_expired(self) -> int:
        """TTL enforcement — the DynamoDB TTL delete + S3 lifecycle rule
        (dynamodb.tf:111-115,144-148; s3.tf:22-28)."""
        now = time.time()
        with self._lock, self._conn:
            spilled = self._conn.execute(
                "SELECT spill_path FROM variant_query_responses"
                " WHERE expires_at <= ? AND spill_path IS NOT NULL",
                (now,),
            ).fetchall()
            n = self._conn.execute(
                "DELETE FROM variant_queries WHERE expires_at <= ?", (now,)
            ).rowcount
            n += self._conn.execute(
                "DELETE FROM variant_query_responses WHERE expires_at <= ?",
                (now,),
            ).rowcount
        for (p,) in spilled:
            Path(p).unlink(missing_ok=True)
        return n

    def checkpoint(self) -> None:
        """WAL checkpoint + truncate — called from the runner's writer
        thread between batches, so no commit ever absorbs the
        checkpoint fsync (auto-checkpoint is disabled) and no request
        waits for it."""
        with self._lock:
            self._conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")

    def close(self) -> None:
        with self._lock:
            self._conn.close()


#: what ``close()`` queues behind the last job: the writer writes
#: everything ahead of it, then leaves
_CLOSE = object()


class AsyncQueryRunner:
    """Background execution + result caching over a :class:`QueryJobTable`.

    ``submit`` hashes the payload and decides from memory: a finished
    job's hand-off answers a repeat, a job in flight takes the caller on
    (single-flight), a row a restart left answers from the table, and
    anything else is claimed and runs ``engine.search`` on a worker
    thread. A job that finished whole goes to the one writer thread,
    which stores the per-(dataset,vcf) response set through the job table
    (spill included) in grouped commits; ``poll``/``result`` give the
    async API surface the reference's RUNNING/COMPLETED envelope switch
    needs (route_g_variants.py:199-214 elif status == JobStatus.RUNNING).
    """

    #: seconds between the writer thread's sweeps: the table's TTL purge
    #: and WAL checkpoint, the expired hand-offs and restored ids
    PURGE_INTERVAL_S = 60.0
    #: in-memory lifetime of a PARTIAL (replicas-down, degraded) result:
    #: long enough to hand to the waiters coalesced onto the job, far
    #: too short to serve as a cached answer after the routes heal
    PARTIAL_HANDOFF_TTL_S = 5.0

    def __init__(
        self,
        engine,
        table: QueryJobTable,
        *,
        workers: int | None = None,
        max_pending: int | None = None,
    ):
        self.engine = engine
        self.table = table
        res = getattr(
            getattr(engine, "config", None), "resilience", None
        )
        # explicit None checks, not `or`: a configured 0 must fail
        # loudly (ThreadPoolExecutor / AdmissionController raise), not
        # silently coerce to the default. Fallback defaults read the
        # ResilienceConfig field declarations — ONE source, so an env
        # override (BEACON_SHED_RETRY_AFTER_S etc.) can never diverge
        # between the server gate and this runner gate.
        if workers is None:
            workers = getattr(
                res, "runner_workers", ResilienceConfig.runner_workers
            )
        if max_pending is None:
            max_pending = getattr(
                res, "runner_max_pending", ResilienceConfig.runner_max_pending
            )
        self.workers = workers
        self.max_pending = max_pending
        self.shed_retry_after_s = getattr(
            res, "shed_retry_after_s", ResilienceConfig.shed_retry_after_s
        )
        # lane-aware admission (shaping.py lanes): the bulk lane may
        # hold at most this share of the pending slots, so a record-
        # retrieval flood saturates its share while interactive
        # submissions keep admitting
        bulk_share = getattr(
            res, "runner_bulk_share", ResilienceConfig.runner_bulk_share
        )
        self._bulk_cap = max(1, int(self.max_pending * bulk_share))
        self._bulk_active = 0
        # single-flight observability: identical in-flight queries
        # collapsed onto a leader's pending result
        self._coalesced = 0
        # bounded pool, NOT thread-per-query: a flood of distinct
        # queries used to spawn one unbounded thread each — under
        # adversarial load that is a fork bomb with extra steps. The
        # pool bounds concurrency; the admission gate bounds the queue
        # behind it (excess submissions shed 429, never silently pile
        # up) — same mechanism as the server-level gate, acquired here
        # and released from the pool thread.
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="query-runner"
        )
        self._gate = AdmissionController(
            self.max_pending, retry_after_s=self.shed_retry_after_s
        )
        # the jobs in flight, by id: the single-flight registry AND the
        # claim (whoever inserts the event runs the job); waiters block
        # on the event
        self._done: dict[str, threading.Event] = {}
        # the finished jobs' hand-off: (responses, expiry, unavailable,
        # clock reading of the hand-off) — waiters and repeats inside
        # the TTL read these; sqlite is not on their way
        self._results: dict[str, tuple] = {}
        # guards _done, _results, table.restored and the counters below
        self._lock = threading.Lock()
        # admission-wait decomposition: submit -> execution start on
        # the bounded pool (the stage BEFORE the batcher's queue wait)
        # is the ``runner.wait`` stage; the runner.queue_wait_ms
        # histogram feeds once an app registry wires it
        self._wait_hist = None
        # how ``submit`` answered: from the in-memory hand-off, from
        # the job table, or neither (claimed, coalesced or shed)
        self._n_submits = 0
        self._n_memory_hits = 0
        self._n_table_hits = 0
        # the write-behind journal: finished jobs wait here for the one
        # thread that writes to the table. Its counters are its own.
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._n_persisted_jobs = 0
        self._n_persist_commits = 0
        self._n_persist_expired = 0
        self._next_sweep = time.monotonic() + self.PURGE_INTERVAL_S
        self._writer = threading.Thread(
            target=self._write_loop, name="query-jobs-writer", daemon=True
        )
        self._writer.start()

    def close(self) -> None:
        """Stop the pool, and let the writer write what is queued before
        it leaves: the caller closes the table next."""
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._queue.put(_CLOSE)
        self._writer.join()

    def metrics(self) -> dict:
        gate = self._gate.metrics()
        with self._lock:
            coalesced, bulk_active = self._coalesced, self._bulk_active
        return {
            "workers": self.workers,
            "max_pending": self.max_pending,
            "active": gate["in_flight"],
            "shed": gate["shed"],
            "coalesced": coalesced,
            "bulk_active": bulk_active,
            "bulk_cap": self._bulk_cap,
        }

    def register_metrics(self, registry) -> None:
        """The runner pool's typed instruments (its slice of the old
        hand-assembled ``/metrics`` dict, now stable named series)."""
        registry.gauge(
            "runner.workers",
            "async query runner pool size",
            fn=lambda: self.workers,
        )
        registry.gauge(
            "runner.max_pending",
            "runner admission cap",
            fn=lambda: self.max_pending,
        )
        registry.gauge(
            "runner.active",
            "queries executing or queued in the runner",
            fn=lambda: self._gate.metrics()["in_flight"],
        )
        registry.counter(
            "runner.shed",
            "runner submissions shed with 429",
            fn=lambda: self._gate.metrics()["shed"],
        )
        registry.counter(
            "runner.coalesced",
            "identical in-flight queries collapsed onto a leader",
            fn=lambda: self._coalesced,
        )
        registry.gauge(
            "runner.bulk_active",
            "bulk-lane submissions holding runner slots",
            fn=lambda: self._bulk_active,
        )
        registry.counter(
            "runner.submits",
            "queries submitted to the runner",
            fn=lambda: self._n_submits,
        )
        registry.counter(
            "runner.memory_hits",
            "submits answered by the in-memory result hand-off "
            "(a repeat inside the query TTL: no search, no cache lookup)",
            fn=lambda: self._n_memory_hits,
        )
        registry.counter(
            "runner.table_hits",
            "submits answered COMPLETED by the job table "
            "(a row that survived a restart)",
            fn=lambda: self._n_table_hits,
        )
        registry.counter(
            "runner.persisted_jobs",
            "finished jobs the writer thread stored in the job table",
            fn=lambda: self._n_persisted_jobs,
        )
        registry.counter(
            "runner.persist_commits",
            "job-table transactions the writer thread committed "
            "(each stores every job that was waiting)",
            fn=lambda: self._n_persist_commits,
        )
        registry.counter(
            "runner.persist_expired",
            "finished jobs skipped by the writer: their TTL had "
            "lapsed before it reached them",
            fn=lambda: self._n_persist_expired,
        )
        registry.gauge(
            "runner.persist_queue",
            "finished jobs waiting for the writer thread",
            fn=self._queue.qsize,
        )
        # the admission-wait slice of the queue-wait decomposition
        # (/debug/status composes it ahead of the batcher stages)
        self._wait_hist = registry.histogram(
            "runner.queue_wait_ms",
            "async-runner submit -> execution-start wait",
        )

    def _note_queue_wait(self, wait_ms: float, job_ctx=None) -> None:
        tracer.observe("runner.wait", wait_ms, ctxs=(job_ctx,))
        h = self._wait_hist
        if h is not None:
            h.observe(wait_ms)

    def queue_wait_summary(self) -> dict:
        """Percentiles of the runner's admission wait (the
        ``runner.wait`` stage's ring; empty dict before any async
        execution) — same summary semantics as every other stage in
        /debug/status."""
        return tracer.stage_quantiles("runner.wait")

    # -- the writer thread ---------------------------------------------------

    def _write_loop(self) -> None:
        """Everything that writes to the table runs here, and nowhere
        else. Take all that is queued, store it in one transaction,
        loop: a job waits for the commit ahead of it and for nothing
        else. An empty queue is waited on until the next sweep is due."""
        log = logging.getLogger(__name__)
        while True:
            batch, closing = [], False
            try:
                job = self._queue.get(
                    timeout=max(0.0, self._next_sweep - time.monotonic())
                )
                while True:
                    if job is _CLOSE:
                        closing = True
                    else:
                        batch.append(job)
                    job = self._queue.get_nowait()
            except queue.Empty:
                pass
            # this thread must outlive a failed commit (a full disk, an
            # injected fault): the jobs stay served from memory for
            # their TTL and are only not there after a restart
            try:
                self._persist(batch)
            except Exception:
                log.exception("job table: %d jobs not stored", len(batch))
            if closing:
                return
            if time.monotonic() >= self._next_sweep:
                try:
                    self._sweep()
                except Exception:
                    log.exception("job table: sweep failed")

    def _persist(self, batch: list[FinishedJob]) -> None:
        now = time.time()
        live = [job for job in batch if job.expires_at > now]
        self._n_persist_expired += len(batch) - len(live)
        if not live:
            return
        # one sample a transaction, serving its jobs
        with tracer.serving(len(live)), stage("runner.persist"):
            self.table.write_jobs(live)
        self._n_persisted_jobs += len(live)
        self._n_persist_commits += 1

    def _sweep(self) -> None:
        """TTL enforcement, once a ``PURGE_INTERVAL_S``: the table's
        expired rows and spills, its WAL (a checkpoint fsync of 1-2 s on
        a busy disk: why it runs here, where no request waits for it),
        the expired hand-offs and restored ids."""
        self._next_sweep = time.monotonic() + self.PURGE_INTERVAL_S
        self.table.purge_expired()
        self.table.checkpoint()
        now = time.time()
        restored = self.table.restored
        with self._lock:
            for q in [q for q, hit in self._results.items() if hit[1] <= now]:
                del self._results[q]
            for q in [q for q, exp in restored.items() if exp <= now]:
                del restored[q]

    # -- the request's side --------------------------------------------------

    def submit(
        self, payload, *, fingerprint: str | None = None
    ) -> tuple[str, JobStatus]:
        """``fingerprint`` (e.g. the engine's index fingerprint) is folded
        into the query hash so cached results die with the data they were
        computed from."""
        with stage("runner.lookup"):
            return self._submit(payload, fingerprint)

    def _submit(self, payload, fingerprint) -> tuple[str, JobStatus]:
        # the payload's fields as they are: ``dataclasses.asdict`` copies
        # every container element by element in Python, 18,191 sample
        # names a request at biobank width, for the same JSON
        query_id = hash_query(
            {"payload": fields_of(payload), "fp": fingerprint}
        )
        # lane-aware admission: the ambient lane note (set by the API
        # layer's classifier) decides whether this submission draws
        # from the bulk share of the pending slots
        job_ctx = current_context()
        bulk = job_ctx is not None and job_ctx.notes.get("lane") == "bulk"
        # ONE critical section looks the id up and, on a miss, claims
        # it: whoever inserts into ``_done`` runs the job, so concurrent
        # identical requests run once. No sqlite statement is on this
        # path: memory knows every job of this process, ``restored``
        # every row of the last one.
        with self._lock:
            self._n_submits += 1
            now = time.time()
            hit = self._results.get(query_id)
            if hit is not None and hit[1] > now:
                # authoritative the moment the search finished (the
                # table may not have the rows yet)
                self._n_memory_hits += 1
                outcome = "memory_hit"
            elif query_id in self._done:
                # single-flight: coalesce onto the in-flight execution
                # — consumes no pool slot, so it comes before the
                # capacity gate and the bulk-lane cap (a follower
                # attaches to the leader's pending result, it adds no
                # work) and is never shed
                self._coalesced += 1
                outcome = "coalesced"
            elif self.table.restored.get(query_id, 0.0) > now:
                self._n_table_hits += 1
                outcome = "table_hit"
            elif bulk and self._bulk_active >= self._bulk_cap:
                outcome = "bulk_shed"
            elif not self._gate.try_acquire():
                # a pool slot is reserved BEFORE the claim: a claim
                # that is then shed would leave waiters coalesced onto
                # a job nobody executes
                outcome = "shed"
            else:
                outcome = "claimed"
                self._bulk_active += bulk
                self._done[query_id] = done = threading.Event()
                self._results.pop(query_id, None)  # expired
                self.table.restored.pop(query_id, None)  # expired
        if outcome == "bulk_shed":
            raise Overloaded(
                f"query runner bulk lane at capacity "
                f"({self._bulk_cap} of {self.max_pending} slots)",
                retry_after_s=self.shed_retry_after_s,
            )
        if outcome == "shed":
            raise Overloaded(
                f"query runner at capacity ({self.max_pending} pending)",
                retry_after_s=self.shed_retry_after_s,
            )
        if outcome != "claimed":
            # job-layer outcome notes (telemetry): a repeat served here
            # never reaches engine.search, so the slow-query log would
            # otherwise show an unexplained fast request
            annotate(query_job=outcome)
            if outcome == "coalesced":
                return query_id, JobStatus.RUNNING
            return query_id, JobStatus.COMPLETED

        pl = dataclasses.replace(payload, query_id=query_id)
        # the SPAWNING request's deadline rides into the worker thread
        # (thread-locals don't cross): the search abandons at its next
        # check-point once the deadline lapses — worker calls clamp,
        # expired batches refuse to launch. A coalescer with a longer
        # deadline simply sees the abandoned job and falls back to a
        # direct search under its own deadline. The request context
        # (trace id + outcome notes) crosses the same way, so spans
        # recorded on the pool thread — and the trace header on any
        # coordinator->worker hop — keep the ingress trace id.
        job_deadline = current_deadline()
        t_start = time.time()
        t_enqueue = time.perf_counter()

        def release():
            self._gate.release()
            with self._lock:
                self._bulk_active -= bulk
                self._done.pop(query_id, None)

        def run():
            self._note_queue_wait(
                (time.perf_counter() - t_enqueue) * 1e3, job_ctx
            )
            with request_context(job_ctx), span(
                "query_jobs.run", query_id=query_id
            ):
                try:
                    with deadline_scope(job_deadline):
                        responses = self.engine.search(pl)
                    # a DEGRADED answer (some datasets had no reachable
                    # replica — dispatch annotated unavailable_datasets
                    # on the request context) must not be cached as THE
                    # answer for the query TTL: it is handed to the
                    # waiters coalesced onto this job and kept a few
                    # seconds, never stored, so later identical queries
                    # re-execute against the (possibly healed) routes
                    # instead of replaying a stale empty result
                    unavailable = tuple(
                        job_ctx.notes.get("unavailable_datasets") or ()
                        if job_ctx is not None
                        else ()
                    )
                    ttl = (
                        self.PARTIAL_HANDOFF_TTL_S
                        if unavailable
                        else self.table.query_ttl_s
                    )
                    # the unavailable set rides WITH the cached handoff:
                    # a coalesced waiter (different request context)
                    # must get the partial marking too, not a silently
                    # incomplete answer
                    # (last rides the clock reading the hand-off was made
                    # at: the woken waiter's ``handoff.back`` starts there)
                    end_time = time.time()
                    with self._lock:
                        self._results[query_id] = (
                            responses,
                            end_time + ttl,
                            unavailable,
                            time.perf_counter(),
                        )
                    # waiters are served from the in-memory handoff the
                    # moment the search finishes. The rows are for a
                    # restart: the writer thread stores them, so that
                    # neither this worker, its admission slot nor any
                    # request waits for sqlite
                    done.set()
                    if not unavailable:
                        self._queue.put(
                            FinishedJob(
                                query_id, responses, t_start, end_time,
                                end_time + ttl,
                            )
                        )
                except Exception:
                    # never cache a failure as an empty result: drop the
                    # job so pollers fall back to a direct search (which
                    # surfaces the real error to the caller)
                    logging.getLogger(__name__).exception(
                        "async query %s failed", query_id
                    )
                    with self._lock:
                        self._results.pop(query_id, None)
                finally:
                    done.set()
                    release()

        try:
            self._pool.submit(run)
        except RuntimeError:
            # pool shut down (close() raced a late submit): release
            # everything so the job doesn't read RUNNING forever
            release()
            raise
        return query_id, JobStatus.RUNNING

    def poll(self, query_id: str) -> JobStatus:
        """The job as this process knows it: in flight, answerable
        (a whole result inside its TTL, in memory or restored), or NEW.
        A degraded result is handed to waiters, never COMPLETED."""
        now = time.time()
        with self._lock:
            if query_id in self._done:
                return JobStatus.RUNNING
            hit = self._results.get(query_id)
            if hit is not None and hit[1] > now and not hit[2]:
                return JobStatus.COMPLETED
            if self.table.restored.get(query_id, 0.0) > now:
                return JobStatus.COMPLETED
        return JobStatus.NEW

    def result(
        self, query_id: str, *, wait_s: float = 0.0
    ) -> list[VariantSearchResponse] | None:
        """Responses if COMPLETED (optionally waiting), else None.
        The wait is clamped by the caller's ambient request deadline."""
        waited = False
        if wait_s > 0:
            with self._lock:
                ev = self._done.get(query_id)
            if ev is not None:
                # block on the job's completion event (no poll)
                ev.wait(current_deadline().clamp(wait_s))
                waited = True
        with self._lock:
            hit = self._results.get(query_id)
            restored = self.table.restored.get(query_id, 0.0)
        now = time.time()
        if hit is not None and hit[1] > now:
            if waited:
                # parked on the job's event until the worker handed the
                # result over: from its clock reading to this thread
                # running again
                tracer.observe(
                    "handoff.back",
                    (time.perf_counter() - hit[3]) * 1e3,
                    ctxs=(current_context(),),
                )
            if hit[2]:
                # replay the partial marking onto THIS caller's request
                # context — the job thread annotated the submitter's,
                # and a coalesced waiter has its own
                annotate(unavailable_datasets=hit[2])
            return hit[0]
        if restored > now:
            # the one read of the table a request makes: a job of the
            # process before this one
            return self.table.get_responses(query_id)
        return None
