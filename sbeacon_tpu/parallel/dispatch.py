"""Cross-host query dispatch: the DCN tier of the comm backbone.

SURVEY.md §2.5/§5: inside a pod, fan-out/fan-in is one compiled program
over ICI (``mesh.py`` — psum/all_gather replace the SNS/DynamoDB barrier
apparatus entirely); *across hosts*, the reference's process boundary —
SNS messages / direct Lambda invokes carrying ``SplitQueryPayload`` /
``PerformQueryResponse`` JSON (reference: sns.tf, variantutils/
local_utils.py:37-44, splitQuery/lambda_function.py:28-35) — becomes a
thin typed-payload dispatcher: each worker host owns a set of dataset
index shards behind a :class:`WorkerServer`; the coordinator's
:class:`DistributedEngine` routes a ``VariantQueryPayload`` to the
workers owning its datasets (thread-pool scatter, the reference's
ThreadPoolExecutor(500) shape), retries transient failures (the
reference's 10x save / retry loops), and merges the per-(dataset,vcf)
response lists — presenting the exact ``VariantEngine`` interface so the
API layer, job table, and micro-batcher compose unchanged. Datasets
served by several workers keep their full replica list
(:class:`ReplicaRouter`): power-of-two-choices routing over recent
RTTs, failover to the next replica on worker errors or open circuits,
replica-hedged searches for slow primaries, partial-results
degradation when every copy is down, and a background rediscovery loop
that heals routes — the fault tolerance the reference inherited from
Lambda invoke retries, made explicit.

Transport is stdlib HTTP+JSON (the payload types' stable dict form)
over the pooled keep-alive layer in ``transport.py`` (per-worker
connection pools, hedged scans, gzip bodies); inject ``post=``/``get=``
callables to swap in gRPC/DCN transport in a pod deployment. For
multi-host *compute* (one jit program spanning hosts), see
``init_multihost`` — jax.distributed over the same coordinator model.
"""

from __future__ import annotations

import base64
import collections
import dataclasses
import gzip
import hmac
import json
import logging
import random
import threading
import time
import urllib.error
import concurrent.futures as futures_mod
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..harness.faults import fault_point
from .transport import (
    PooledTransport,
    note_hedge,
    register_transport_metrics,
    urllib_get,
    urllib_post,
    urllib_post_bytes,
)
from ..payloads import (
    SliceScanPayload,
    VariantQueryPayload,
    VariantSearchResponse,
)
from .. import telemetry as telemetry_mod
from ..plan import plan_stage
from ..resilience import (
    CLOSED,
    OPEN,
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
    current_deadline,
    register_breaker_metrics,
)
from ..telemetry import (
    TRACE_HEADER,
    RequestContext,
    annotate,
    charge_cost,
    current_context,
    new_span_id,
    publish_event,
    request_context,
    sanitize_trace_id,
)
from ..utils.trace import Span, span

log = logging.getLogger(__name__)


# -- hedging kill-switch ------------------------------------------------------

#: process-wide hedge enable flag: the brownout ladder's FIRST rung
#: (shaping.BrownoutLadder via set_hedging_enabled) — under a sustained
#: SLO breach the cheapest load to shed is the duplicate calls hedging
#: adds, before any request is refused. Process-global like the fault
#: injector: scan pools and replica routers live below the app layer.
_hedging_enabled = True


def set_hedging_enabled(enabled: bool) -> None:
    """Flip the process-wide hedging kill-switch (brownout rung 1).
    Affects the adaptive/fixed hedge delay computation in BOTH the
    ingest scan pool and the replica-hedged search path; in-flight
    hedges are unaffected."""
    global _hedging_enabled
    _hedging_enabled = bool(enabled)


def hedging_enabled() -> bool:
    return _hedging_enabled


# -- worker side --------------------------------------------------------------


def _make_handler(
    engine, token: str = "", open_scan: bool = False, reload_fn=None
):
    class Handler(BaseHTTPRequestHandler):
        # keep-alive: the coordinator's pooled transport holds a few
        # persistent connections per worker instead of a TCP handshake
        # (and a ThreadingHTTPServer thread spawn) per call
        protocol_version = "HTTP/1.1"
        # reap idle keep-alive connections a little after the
        # coordinator's pool TTL would have evicted them anyway
        timeout = 120.0

        def log_message(self, *a):  # quiet
            pass

        def _read_body(self) -> bytes:
            """The full request body, gunzipped when the coordinator
            compressed it (transport.py gzip_min_bytes)."""
            n = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(n) if n else b""
            if self.headers.get("Content-Encoding", "").lower() == "gzip":
                raw = gzip.decompress(raw)
            return raw

        def _send(self, status: int, payload):
            body = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _authorized(self) -> bool:
            # shared-token gate on the worker boundary (the reference's
            # equivalent — direct Lambda invoke/SNS — was IAM-gated);
            # /health stays open for liveness probes
            if not token:
                return True
            got = self.headers.get("Authorization", "")
            # bytes compare: compare_digest raises TypeError on non-ASCII
            # str, which would kill the request with no response
            return hmac.compare_digest(
                got.encode(), f"Bearer {token}".encode()
            )

        def do_GET(self):
            if self.path == "/health":
                self._send(200, {"ok": True})
            elif not self._authorized():
                self._send(401, {"error": "unauthorized"})
            elif self.path == "/ops/digest":
                # the fleet-federation exchange payload (ISSUE 12):
                # bounded worker health/freshness digest, behind the
                # SAME worker-token boundary as /search — the digest
                # names datasets and fingerprints, which are data-plane
                # metadata, not public probe output
                self._send(200, ops_digest(engine))
            elif self.path == "/datasets":
                # per-dataset fingerprints let the coordinator group
                # only IDENTICAL shard copies as replicas (a worker
                # serving a stale copy of one dataset must not be
                # treated as interchangeable with a fresh one)
                ds_fps = getattr(engine, "dataset_fingerprints", None)
                self._send(
                    200,
                    {
                        "datasets": engine.datasets(),
                        "fingerprint": engine.index_fingerprint(),
                        "dataset_fingerprints": (
                            ds_fps() if ds_fps is not None else {}
                        ),
                    },
                )
            else:
                self._send(404, {"error": "not found"})

        def _send_bytes(self, status: int, body: bytes):
            self.send_response(status)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            # the body is read BEFORE any early return: with HTTP/1.1
            # keep-alive, unread body bytes would bleed into the next
            # request's parse on this connection
            try:
                raw = self._read_body()
            except Exception:
                self._send(400, {"error": "bad request body"})
                return
            if not self._authorized():
                self._send(401, {"error": "unauthorized"})
                return
            if self.path == "/reload":
                # re-pin shards from storage (a coordinator that ingested
                # into shared storage tells workers to pick the new
                # shards up without a process restart)
                if reload_fn is None:
                    self._send(404, {"error": "reload not wired"})
                    return
                try:
                    n = reload_fn()
                    self._send(200, {"ok": True, "shards": int(n)})
                except Exception as e:
                    log.exception("worker reload failed")
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            if self.path.startswith("/migrate/"):
                # live-migration artifact plane (ISSUE 16): manifest /
                # fetch / adopt / drop, all POST (keep-alive-safe
                # bodies), all inside the SAME worker-token boundary
                # as /search and /reload — migration widens no trust
                # surface. Served only when the engine grows the
                # migration seams; a worker running an engine shape
                # without them answers 404.
                self._do_migrate(raw)
                return
            if self.path == "/scan":
                # /scan range-reads a CLIENT-SUPPLIED location (local path
                # or URL) — an SSRF/arbitrary-read primitive if exposed.
                # Secure by default: only served when a shared token gates
                # the worker, or when the operator opted in explicitly
                # (in-process tests, airtight private networks).
                if not token and not open_scan:
                    self._send(
                        403,
                        {
                            "error": "scan requires a worker token "
                            "(or --open-scan on a private network)"
                        },
                    )
                    return
                self._do_scan(raw)
                return
            if self.path != "/search":
                self._send(404, {"error": "not found"})
                return
            try:
                t_recv = time.perf_counter()
                # from_doc drops unknown keys: this worker must keep
                # answering a coordinator one payload-field ahead of it
                payload = VariantQueryPayload.from_doc(json.loads(raw))
                # adopt the coordinator's trace id (X-Beacon-Trace) so
                # worker-side spans parent into the same distributed
                # trace; a direct caller without the header gets a
                # fresh worker-local id
                ctx = RequestContext(
                    trace_id=sanitize_trace_id(
                        self.headers.get(TRACE_HEADER)
                    ),
                    route="worker.search",
                )
                with request_context(ctx), span(
                    "worker.search",
                    datasets=len(payload.dataset_ids or []),
                ):
                    t_eng = time.perf_counter()
                    responses = engine.search(payload)
                    engine_s = time.perf_counter() - t_eng
                t_ser = time.perf_counter()
                docs = [dataclasses.asdict(r) for r in responses]
                serialize_s = time.perf_counter() - t_ser
                # the span-summary side channel (ISSUE 12): a compact
                # worker-stage decomposition the coordinator grafts as
                # child spans into its own trace tree — the worker's
                # time stops being an opaque RTT. ``queueMs`` is the
                # micro-batch wait when the engine annotated one;
                # ``cache`` the response-cache outcome; ``rows`` the
                # matched rows shipped back. Bounded and additive: an
                # old coordinator ignores the extra key.
                notes = ctx.notes
                try:
                    queue_ms = float(notes.get("batch_ms") or 0.0)
                except (TypeError, ValueError):
                    queue_ms = 0.0
                # the batch wait happened INSIDE engine.search: report
                # engine time EXCLUSIVE of it so the grafted stages lay
                # out sequentially without double-counting the queue
                engine_excl_ms = max(engine_s * 1e3 - queue_ms, 0.0)
                self._send(
                    200,
                    {
                        "responses": docs,
                        "meta": {
                            "spanId": new_span_id(),
                            "queueMs": round(queue_ms, 3),
                            "engineMs": round(engine_excl_ms, 3),
                            "serializeMs": round(serialize_s * 1e3, 3),
                            "totalMs": round(
                                (time.perf_counter() - t_recv) * 1e3, 3
                            ),
                            "rows": sum(len(r.variants) for r in responses),
                            "cache": notes.get("response_cache", ""),
                            "datasets": len(payload.dataset_ids or []),
                        },
                    },
                )
            except Exception as e:  # worker errors travel to coordinator
                log.exception("worker search failed")
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

        def _do_scan(self, raw: bytes):
            """Ingest slice-scan leaf (the summariseSlice worker role):
            range-read + parse + build one slice shard, returned as a raw
            npz blob. The VCF location must be reachable from the worker
            (shared filesystem or object-store URL)."""
            try:
                from ..index.columnar import dumps_index
                from ..ingest.pipeline import scan_slice_to_shard

                p = SliceScanPayload(**json.loads(raw))
                shard = scan_slice_to_shard(
                    p.vcf_location,
                    p.vstart,
                    p.vend,
                    dataset_id=p.dataset_id,
                    sample_names=p.sample_names,
                )
                self._send_bytes(200, dumps_index(shard))
            except Exception as e:
                log.exception("worker slice scan failed")
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

        def _do_migrate(self, raw: bytes):
            """Shard-migration artifact exchange: ``manifest`` lists a
            dataset's base + standing-delta artifacts by epoch-ranged
            fingerprint (the resume key), ``fetch`` streams one as a
            raw npz blob, ``adopt`` installs a received artifact at its
            ORIGINAL epoch, and ``drop`` retires the dataset after
            cut-over. Every seam is getattr-guarded: a worker embedding
            an engine without the migration entry points answers 404,
            and the controller reports it instead of half-migrating."""
            from ..index.columnar import dumps_index, loads_index

            op = self.path[len("/migrate/"):]
            try:
                doc = json.loads(raw) if raw else {}
                if not isinstance(doc, dict):
                    raise ValueError("migrate body must be an object")
            except Exception:
                self._send(400, {"error": "bad migrate body"})
                return
            ds = str(doc.get("dataset") or "")
            try:
                if op == "manifest":
                    fn = getattr(engine, "migration_manifest", None)
                    if fn is None:
                        self._send(
                            404, {"error": "migration not supported"}
                        )
                    else:
                        self._send(200, fn(ds))
                elif op == "fetch":
                    fn = getattr(engine, "export_artifact", None)
                    if fn is None:
                        self._send(
                            404, {"error": "migration not supported"}
                        )
                        return
                    shard = fn(
                        ds,
                        str(doc.get("vcf") or ""),
                        epoch=doc.get("epoch"),
                    )
                    if shard is None:
                        self._send(404, {"error": "artifact not found"})
                    else:
                        self._send_bytes(200, dumps_index(shard))
                elif op == "adopt":
                    shard = loads_index(
                        base64.b64decode(doc.get("blob") or "")
                    )
                    if doc.get("kind") == "delta":
                        fn = getattr(engine, "adopt_delta", None)
                        if fn is None:
                            self._send(
                                404,
                                {"error": "migration not supported"},
                            )
                            return
                        adopted = fn(shard, int(doc.get("epoch") or 0))
                        self._send(
                            200, {"ok": True, "adopted": bool(adopted)}
                        )
                    else:
                        engine.add_index(shard)
                        self._send(200, {"ok": True, "adopted": True})
                elif op == "drop":
                    fn = getattr(engine, "drop_dataset", None)
                    if fn is None:
                        self._send(
                            404, {"error": "migration not supported"}
                        )
                    else:
                        self._send(
                            200, {"ok": True, "shards": int(fn(ds))}
                        )
                else:
                    self._send(404, {"error": "not found"})
            except Exception as e:
                log.exception("worker migrate %s failed", op)
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler


class _WorkerHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that tracks live client connections so
    shutdown can sever them. A killed worker process takes every
    socket with it; ``server_close`` alone only closes the LISTENER,
    leaving keep-alive handler threads answering on pooled
    coordinator connections — a zombie that would mask exactly the
    dead-worker failover paths the replica layer (and its tests)
    exist for."""

    daemon_threads = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._conn_lock = threading.Lock()
        self._conns: set = set()

    def process_request(self, request, client_address):
        with self._conn_lock:
            self._conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conn_lock:
            self._conns.discard(request)
        super().shutdown_request(request)

    def handle_error(self, request, client_address):
        # a handler mid-write when close_all_connections severed its
        # socket raises BrokenPipe/ConnectionReset — that IS the
        # faithful kill, not an error worth a stderr traceback
        import sys

        exc = sys.exc_info()[1]
        if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
            return
        super().handle_error(request, client_address)

    def close_all_connections(self) -> None:
        import socket as socket_mod

        with self._conn_lock:
            conns, self._conns = list(self._conns), set()
        for sock in conns:
            try:
                sock.shutdown(socket_mod.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


class WorkerServer:
    """One worker host's engine behind HTTP (the performQuery leaf's
    process boundary, minus SNS)."""

    def __init__(
        self,
        engine,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        token: str = "",
        open_scan: bool = False,
        reload_fn=None,
    ):
        self.engine = engine
        self.server = _WorkerHTTPServer(
            (host, port),
            _make_handler(engine, token, open_scan, reload_fn),
        )
        self.thread: threading.Thread | None = None

    @property
    def address(self) -> str:
        h, p = self.server.server_address[:2]
        return f"http://{h}:{p}"

    def start_background(self) -> "WorkerServer":
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()
        return self

    def shutdown(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        # faithful kill: live keep-alive connections die with the
        # server, like the process death they stand in for
        self.server.close_all_connections()


#: datasets/fingerprints listed per digest before truncation — the
#: digest must stay a bounded control-plane message, never a data dump
DIGEST_DATASET_CAP = 128


def ops_digest(engine, extras: dict | None = None) -> dict:
    """The bounded worker-health digest served at ``/ops/digest`` (the
    fleet-federation exchange payload, ISSUE 12): per-dataset identity
    (the divergence signal), delta-tail depth/rows (the freshness-lag
    signal), delta publishes, and open breakers. Every field reads
    lock-free engine snapshots — a digest poll must answer while a
    stack rebuild holds the publish lock. ``extras`` lets an embedded
    coordinator add its app-tier signals (SLO breaches, slow-query
    count, top cost tenants); a bare worker host serves the engine
    fields alone."""
    base_fp = getattr(engine, "base_fingerprint", None)
    ds_fps_fn = getattr(engine, "dataset_fingerprints", None)
    delta_stats = getattr(engine, "delta_stats", None)
    delta_metrics = getattr(engine, "delta_metrics", None)
    datasets = engine.datasets()
    ds_fps = dict(
        sorted((ds_fps_fn() if ds_fps_fn is not None else {}).items())[
            :DIGEST_DATASET_CAP
        ]
    )
    breakers: list[str] = []
    breaker = getattr(engine, "breaker", None)
    if breaker is not None:
        breakers = sorted(
            u
            for u, d in breaker.metrics().items()
            if d.get("state") != "closed"
        )
    doc = {
        "time": time.time(),
        "datasets": datasets[:DIGEST_DATASET_CAP],
        "datasetsTotal": len(datasets),
        "baseFingerprint": (
            base_fp() if base_fp is not None else engine.index_fingerprint()
        ),
        "datasetFingerprints": ds_fps,
        "deltaTails": delta_stats() if delta_stats is not None else {},
        "deltaPublishes": (
            delta_metrics().get("publishes", 0)
            if delta_metrics is not None
            else 0
        ),
        "openBreakers": breakers,
        # device-health exchange fields (module attr, not a from-import:
        # the recorder is process-global and tests swap it): a replica
        # quietly recompiling mid-request or padding most of its lanes
        # away shows up in the FLEET view, not just its own /debug
        "midRequestCompiles": (
            telemetry_mod.flight_recorder.mid_request_compiles()
        ),
        "worstPadWaste": telemetry_mod.flight_recorder.worst_pad_waste(),
    }
    if extras:
        doc.update(extras)
    return doc


# -- coordinator side ---------------------------------------------------------
#
# urllib_post / urllib_get / urllib_post_bytes live in transport.py now
# (re-exported above for back-compat): every real coordinator->worker
# call goes through the pooled keep-alive transport, and the unpooled
# fallbacks are kept only as injectable seams and CLI probes.


def register_dispatch_metrics(registry, supplier) -> None:
    """The coordinator fan-out's own series. ``supplier`` returns the
    current :meth:`DistributedEngine.dispatch_stats` dict (empty on
    single-host engines — the app's fallback registration keeps the
    catalogue deployment-stable, like the breaker series)."""

    def field(name):
        return lambda: supplier().get(name, 0)

    registry.counter(
        "dispatch.short_circuits",
        "boolean fan-outs answered before the full worker drain",
        fn=field("short_circuits"),
    )
    registry.counter(
        "dispatch.failovers",
        "worker search legs re-routed to another replica after a failure",
        fn=field("failovers"),
    )
    registry.counter(
        "dispatch.partial_responses",
        "searches answered partially with some datasets unavailable",
        fn=field("partial_responses"),
    )
    registry.gauge(
        "routing.replicas",
        "replica routes in the table (sum of copies across datasets)",
        fn=field("replicas"),
    )
    registry.counter(
        "routing.rediscoveries",
        "background route-rediscovery passes run to heal dead routes",
        fn=field("rediscoveries"),
    )
    # fleet federation (ISSUE 12): the digest-poll plane's own series
    registry.counter(
        "fleet.digest_polls",
        "worker /ops/digest collection passes run by the fleet view",
        fn=field("fleet_polls"),
    )
    registry.gauge(
        "fleet.workers_reachable",
        "workers whose latest digest poll answered",
        fn=field("fleet_reachable"),
    )
    registry.gauge(
        "fleet.divergent_datasets",
        "datasets whose replicas advertise divergent fingerprints",
        fn=field("fleet_divergent"),
    )
    # live shard migration (ISSUE 16): the controller's lifecycle series
    registry.counter(
        "migration.started",
        "shard migrations started (copy phase entered)",
        fn=field("migration_started"),
    )
    registry.counter(
        "migration.completed",
        "shard migrations completed through cut-over",
        fn=field("migration_completed"),
    )
    registry.counter(
        "migration.rolled_back",
        "shard migrations aborted and rolled back (verify mismatch, "
        "crash mid-protocol)",
        fn=field("migration_rolled_back"),
    )
    registry.counter(
        "migration.bytes_copied",
        "artifact bytes streamed source->target by migration copies",
        fn=field("migration_bytes_copied"),
    )


def _graft_worker_spans(wsp, url: str, meta, rtt_s: float) -> None:
    """Adopt one worker leg's side-channel span summary (the ``meta``
    block of a ``/search`` response) as child spans of the
    coordinator's ``dispatch.worker_call`` span — the Dapper
    cross-process assembly the reference's SNS fan-out never had.
    Network time is DERIVED (RTT minus the worker-reported total,
    split evenly around the remote span: the coordinator cannot
    observe the skew) and the worker's queue/engine/serialize stages
    lay out sequentially inside it. No-op when tracing is disabled
    (``wsp`` is the null span) or the worker predates the summary."""
    sp = getattr(wsp, "span", None)
    if sp is None or not isinstance(meta, dict):
        return
    try:
        total_ms = float(meta.get("totalMs") or 0.0)
    except (TypeError, ValueError):
        return
    rtt_ms = rtt_s * 1e3
    net_ms = max(rtt_ms - total_ms, 0.0)
    wsp.note(
        networkMs=round(net_ms, 3),
        workerMs=round(total_ms, 3),
        rows=meta.get("rows", 0),
        cache=meta.get("cache", ""),
    )
    now = time.perf_counter()
    w_start = now - rtt_s + net_ms / 2e3
    remote = Span(
        name="worker.remote",
        t_start=w_start,
        t_end=w_start + total_ms / 1e3,
        meta={
            "url": url,
            "rows": meta.get("rows", 0),
            "cache": meta.get("cache", ""),
            "datasets": meta.get("datasets", 0),
        },
        trace_id=sp.trace_id,
        span_id=str(meta.get("spanId") or new_span_id()),
    )
    t = w_start
    for name, key in (
        ("worker.queue", "queueMs"),
        ("worker.engine", "engineMs"),
        ("worker.serialize", "serializeMs"),
    ):
        try:
            ms = float(meta.get(key) or 0.0)
        except (TypeError, ValueError):
            ms = 0.0
        if ms <= 0.0:
            continue
        remote.children.append(
            Span(
                name=name,
                t_start=t,
                t_end=t + ms / 1e3,
                trace_id=sp.trace_id,
                span_id=new_span_id(),
            )
        )
        t += ms / 1e3
    sp.children.append(remote)


def _fingerprint_freshness(fp: str) -> int:
    """Total indexed rows encoded in a per-dataset fingerprint (the
    ``vcf|variant_count|call_count|n_rows`` base parts and the
    ``vcf#d<epoch>|rows`` standing delta-tail parts, joined by ``&``) —
    the 'newer copy' heuristic for divergent replicas: re-ingestion
    only grows a dataset's row count, so when two workers advertise
    the same dataset with different fingerprints the larger copy is
    the one that saw the latest publish. Only the exact 4-field base
    / 2-field epoch-tagged delta shapes parse; anything else sorts
    oldest — in particular a legacy worker's ENGINE-WIDE fallback
    string (``ds|vcf|vc|cc|rows`` 5-field parts spanning its whole
    corpus) must lose to real per-dataset identity, not out-freshen
    it by summing rows across unrelated datasets."""
    total = 0
    for part in fp.split("&"):
        fields = part.split("|")
        # delta-tail part: "vcf#d<epoch>|rows" (engine.py
        # _rebuild_serving_state_locked) — the tail rows count toward
        # freshness, so a deeper-tail copy out-freshens its base twin
        if len(fields) == 2 and "#d" in fields[0]:
            pass
        elif len(fields) != 4:
            return -1
        try:
            total += int(fields[-1])
        except ValueError:
            return -1
    return total


def _fingerprint_parts(
    fp: str,
) -> tuple[frozenset, frozenset] | None:
    """(base parts, delta-tail parts) of a per-dataset fingerprint, or
    None when any part fails the grammar (legacy engine-wide strings
    stay unsplittable — they never enter the tail-superset relation)."""
    bases, deltas = set(), set()
    for part in fp.split("&"):
        fields = part.split("|")
        if len(fields) == 2 and "#d" in fields[0]:
            deltas.add(part)
        elif len(fields) == 4:
            bases.add(part)
        else:
            return None
    return frozenset(bases), frozenset(deltas)


class ReplicaRouter:
    """Replica selection for the search fan-out.

    The discovery pass publishes a ``dataset -> (replica urls)`` table
    here (only fingerprint-identical copies are grouped); ``pick``
    chooses among the live replicas by power-of-two-choices over the
    recent per-worker RTT record (the selection-granularity mirror of
    the transport's ``transport.rtt_ms`` histogram): sample two, take
    the faster, skip breaker-open routes. One slow or dead host then
    stops attracting traffic without any health-check protocol — the
    RTTs the scatter already measures are the health signal.
    """

    #: recent round-trips kept per replica for the p2c comparison and
    #: the adaptive hedge delay
    RTT_WINDOW = 128
    #: adaptive hedging needs this many completed calls before the p95
    #: means anything; until then no hedge fires
    HEDGE_MIN_SAMPLES = 8
    #: adaptive hedge delay never drops below this (a sub-ms p95 would
    #: hedge every call and double fleet load for nothing)
    HEDGE_FLOOR_S = 0.05

    def __init__(self, breaker: CircuitBreaker, *, rng=None):
        self.breaker = breaker
        # seeded: routing spread is reproducible under test
        self._rng = rng or random.Random(0xBEAC0)
        self._lock = threading.Lock()
        self._table: dict[str, tuple[str, ...]] = {}
        self._rtts: dict[str, collections.deque] = {}
        # migration cut-over pins: (dataset, url) pairs routed OUT.
        # publish() filters them inside its own critical section, so a
        # concurrent rediscovery republish can never resurrect a route
        # the cut-over just retired (the half-routed state the
        # migration invariant forbids).
        self._retired: set[tuple[str, str]] = set()

    # -- table --------------------------------------------------------------

    def publish(self, table: dict[str, tuple[str, ...]]) -> None:
        new = {ds: tuple(urls) for ds, urls in table.items()}
        with self._lock:
            if self._retired:
                new = {
                    ds: tuple(
                        u for u in urls if (ds, u) not in self._retired
                    )
                    for ds, urls in new.items()
                }
            changed = new != self._table
            self._table = new
        if changed:
            # flight-recorder: only actual topology changes are events
            # (the rediscovery loop republishes every pass — an
            # unchanged table is not a transition)
            publish_event(
                "routing.table_publish",
                datasets=len(new),
                replicas=sum(len(u) for u in new.values()),
            )

    def table(self) -> dict[str, tuple[str, ...]]:
        with self._lock:
            return dict(self._table)

    def replicas(self, dataset: str) -> tuple[str, ...]:
        with self._lock:
            return self._table.get(dataset, ())

    def replica_count(self) -> int:
        with self._lock:
            return sum(len(urls) for urls in self._table.values())

    def retire(self, dataset: str, url: str) -> None:
        """Route ``url`` out of ``dataset``'s replica set ATOMICALLY:
        the pin lands and the url leaves the live table inside ONE
        critical section — the migration cut-over's 'retire the source
        in the same critical section that bumps the table' contract.
        Retired pairs also survive republish (see :meth:`publish`)."""
        with self._lock:
            self._retired.add((dataset, url))
            urls = self._table.get(dataset)
            if urls and url in urls:
                self._table[dataset] = tuple(
                    u for u in urls if u != url
                )
        publish_event("routing.route_retired", dataset=dataset, url=url)

    def unretire(self, dataset: str, url: str) -> None:
        """Lift a cut-over pin (rollback, or the source finished
        dropping the dataset and no longer advertises it) — the next
        publish may route the pair again if a worker advertises it."""
        with self._lock:
            self._retired.discard((dataset, url))

    def retired(self) -> set[tuple[str, str]]:
        with self._lock:
            return set(self._retired)

    # -- RTT record ---------------------------------------------------------

    def note_rtt(self, url: str, seconds: float) -> None:
        with self._lock:
            ring = self._rtts.get(url)
            if ring is None:
                ring = self._rtts[url] = collections.deque(
                    maxlen=self.RTT_WINDOW
                )
            ring.append(seconds)

    def _rtt(self, url: str) -> float | None:
        """Median recent RTT, or None for an unmeasured replica (treated
        as fast, so fresh replicas get explored instead of starved)."""
        with self._lock:
            ring = self._rtts.get(url)
            if not ring:
                return None
            s = sorted(ring)
        return s[len(s) // 2]

    def median_rtt_ms(self, url: str) -> float | None:
        """Public median-RTT view (``/debug/status`` worker rollup)."""
        rtt = self._rtt(url)
        return None if rtt is None else round(rtt * 1e3, 2)

    def hedge_delay(self, hedge_delay_s: float | None) -> float | None:
        """Seconds to wait before racing a second replica, with the
        scan-pool semantics unchanged: >0 fixed, 0 adaptive (p95 of
        recent RTTs once enough samples exist), <0/None off. The
        brownout kill-switch (``set_hedging_enabled``) overrides all."""
        d = hedge_delay_s
        if d is None or d < 0 or not _hedging_enabled:
            return None
        if d > 0:
            return d
        with self._lock:
            all_rtts = [v for ring in self._rtts.values() for v in ring]
        if len(all_rtts) < self.HEDGE_MIN_SAMPLES:
            return None
        all_rtts.sort()
        return max(
            all_rtts[int(0.95 * (len(all_rtts) - 1))], self.HEDGE_FLOOR_S
        )

    # -- selection ----------------------------------------------------------

    def live(self, url: str) -> bool:
        """Pure observation — never consumes a half-open probe (the
        call-site ``allow`` gate does that once per attempted call)."""
        return self.breaker.state(url) != OPEN

    def pick(self, dataset: str, *, avoid=()) -> str | None:
        """The replica to route ``dataset`` to, or None when every copy
        is in ``avoid`` (failover exhausted the replica set)."""
        cands = [u for u in self.replicas(dataset) if u not in avoid]
        if not cands:
            return None
        # breaker-open routes are skipped while an alternative exists;
        # with every copy open, route anyway — the call-site gate
        # raises CircuitOpen cheaply and keeps the half-open probing
        live = [u for u in cands if self.live(u)] or cands
        if len(live) == 1:
            return live[0]
        a, b = self._rng.sample(live, 2)
        ra = self._rtt(a) or 0.0
        rb = self._rtt(b) or 0.0
        return a if ra <= rb else b


class ScanWorkerPool:
    """Coordinator-side round-robin scatter of ingest slice scans.

    The pipeline hands each planned slice to ``scan_blob``; failures
    (worker down, auth, scan error) raise WorkerError and the caller
    falls back to scanning locally — a missing worker degrades
    throughput, never correctness (reference analogue: a failed
    summariseSlice lambda's slice stays in the toUpdate set and is
    re-run). A worker that fails trips its circuit (one-strike breaker:
    open for ``cooldown_s``, then a half-open probe) so one wedged host
    cannot stall every slice for a full timeout each (the dead-worker
    exclusion the query-path scatter already has via discovery refresh).

    Scans are *hedged* (Dean & Barroso, The Tail at Scale): when the
    primary worker has not answered within the hedge delay — fixed, or
    adaptive at the p95 of recent scan RTTs — the same slice races on a
    second worker and the first response wins; the loser is abandoned
    (slice scans are idempotent reads, so duplicate execution only
    costs the loser's CPU). One slow host then bounds *its own* calls,
    not every slice routed to it.
    """

    #: adaptive hedging needs this many completed scans before the p95
    #: means anything; until then no hedge fires
    HEDGE_MIN_SAMPLES = 8
    #: adaptive hedge delay never drops below this (a sub-ms p95 would
    #: hedge every call and double cluster load for nothing)
    HEDGE_FLOOR_S = 0.05

    def __init__(
        self,
        worker_urls: list[str],
        *,
        token: str = "",
        timeout_s: float = 120.0,
        retries: int = 1,
        cooldown_s: float = 30.0,
        post_bytes=None,
        hedge_delay_s: float = 0.0,
        transport: PooledTransport | None = None,
        transport_config=None,
    ):
        if not worker_urls:
            raise ValueError("ScanWorkerPool needs at least one worker URL")
        self.worker_urls = list(worker_urls)
        self.token = token
        self.timeout_s = timeout_s
        self.retries = retries
        self.cooldown_s = cooldown_s
        self.hedge_delay_s = hedge_delay_s
        self._owns_transport = False
        if post_bytes is None:
            if transport is None:
                # built here -> owned here: close() releases the
                # sockets (a caller-passed transport stays caller-owned)
                transport = (
                    PooledTransport.from_config(transport_config)
                    if transport_config is not None
                    else PooledTransport()
                )
                self._owns_transport = True
            post_bytes = transport.post_bytes
        self.transport = transport
        self._post_bytes = post_bytes
        self._bytes_ok = bool(getattr(post_bytes, "accepts_bytes", False))
        self._next = 0
        # the round-4 ad-hoc _dead_until cooldown map, generalised: a
        # single failure opens the circuit for cooldown_s (scan slices
        # have a local fallback, so one strike is the right threshold),
        # then a half-open probe readmits the worker on success
        self.breaker = CircuitBreaker(
            failure_threshold=1, reset_timeout_s=cooldown_s
        )
        self._lock = threading.Lock()
        self._rtts: collections.deque = collections.deque(maxlen=128)
        self._hedges = 0
        self._hedge_wins = 0
        self._hedge_exec: ThreadPoolExecutor | None = None

    def close(self) -> None:
        """Release the hedge pool and any owned connection pool."""
        with self._lock:
            pool, self._hedge_exec = self._hedge_exec, None
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        if self._owns_transport and self.transport is not None:
            self.transport.close()

    def _pick(self) -> str:
        with self._lock:
            for _ in range(len(self.worker_urls)):
                url = self.worker_urls[self._next % len(self.worker_urls)]
                self._next += 1
                if self.breaker.allow(url):
                    return url
            # every worker's circuit is open: take the next anyway (it
            # may have recovered; correctness is covered by local
            # fallback)
            url = self.worker_urls[self._next % len(self.worker_urls)]
            self._next += 1
            return url

    def _pick_other(self, avoid: str) -> str | None:
        """A healthy worker other than ``avoid`` (the hedge target), or
        None when the fleet has no alternative."""
        with self._lock:
            for _ in range(len(self.worker_urls)):
                url = self.worker_urls[self._next % len(self.worker_urls)]
                self._next += 1
                if url != avoid and self.breaker.allow(url):
                    return url
        return None

    def _mark_dead(self, url: str) -> None:
        self.breaker.record_failure(url)

    def _auth_headers(self) -> dict | None:
        return (
            {"Authorization": f"Bearer {self.token}"} if self.token else None
        )

    # -- hedging ------------------------------------------------------------

    def _effective_hedge_delay(self) -> float | None:
        """Seconds to wait before racing a second worker, or None when
        hedging is off (disabled, single worker, or adaptive mode
        without enough RTT history yet)."""
        d = self.hedge_delay_s
        if (
            d is None
            or d < 0
            or len(self.worker_urls) < 2
            or not _hedging_enabled
        ):
            return None
        if d > 0:
            return d
        with self._lock:
            if len(self._rtts) < self.HEDGE_MIN_SAMPLES:
                return None
            s = sorted(self._rtts)
        return max(s[int(0.95 * (len(s) - 1))], self.HEDGE_FLOOR_S)

    def _hedge_pool(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._hedge_exec is None:
                # sized for the ingest pipeline's concurrent run_slice
                # callers plus their hedges: a primary queued behind a
                # full pool must be rare (and is hedge-gated below)
                self._hedge_exec = ThreadPoolExecutor(
                    max_workers=max(8, 2 * len(self.worker_urls)),
                    thread_name_prefix="scan-hedge",
                )
            return self._hedge_exec

    def _note_hedge(self, primary: str, hedge: str) -> None:
        with self._lock:
            self._hedges += 1
        note_hedge()  # process-wide transport.hedges counter
        publish_event("scan.hedge", primary=primary, hedge=hedge)

    def stats(self) -> dict:
        with self._lock:
            return {
                "hedges": self._hedges,
                "hedge_wins": self._hedge_wins,
                "rtt_samples": len(self._rtts),
            }

    # -- the scan call ------------------------------------------------------

    def _scan_once(self, url: str, body, headers) -> tuple[int, bytes]:
        """One raw /scan exchange; successful RTTs feed the adaptive
        hedge delay."""
        t0 = time.perf_counter()
        status, out = self._post_bytes(
            f"{url}/scan", body, self.timeout_s, headers
        )
        if status == 200:
            with self._lock:
                self._rtts.append(time.perf_counter() - t0)
        return status, out

    def _settle(
        self, url: str, status: int, out: bytes, last
    ) -> tuple[bytes | None, Exception | None]:
        """Breaker bookkeeping for one answered scan: the blob on 200,
        else the WorkerError to remember."""
        if status == 200:
            self.breaker.record_success(url)
            return out, last
        err = WorkerError(f"{url}: http {status}: {out[:200]!r}")
        if status in (401, 403):
            self._mark_dead(url)
        else:
            # any other HTTP answer proves the worker is ALIVE
            # (the breaker tracks reachability, not scan success —
            # scan errors are handled by retry + local fallback);
            # recording an outcome also releases a half-open probe
            # so a 500-answering worker is not excluded forever
            self.breaker.record_success(url)
        return None, err

    def scan_blob(self, payload: SliceScanPayload) -> bytes:
        """One slice scan on some worker -> the shard's npz blob
        (columnar.dumps_index form), undecoded."""
        # serialize ONCE: a bytes-capable transport ships these bytes
        # verbatim; legacy injected transports still get the dict
        body = (
            payload.dumps().encode()
            if self._bytes_ok
            else json.loads(payload.dumps())
        )
        headers = self._auth_headers()
        last: Exception | None = None
        for _attempt in range(self.retries + 1):
            url = self._pick()
            delay = self._effective_hedge_delay()
            if delay is None:
                try:
                    status, out = self._scan_once(url, body, headers)
                except Exception as e:
                    last = WorkerError(f"{url}: {e}")
                    self._mark_dead(url)
                    continue
                got, last = self._settle(url, status, out, last)
                if got is not None:
                    return got
                continue
            got, last = self._scan_hedged(url, body, headers, delay, last)
            if got is not None:
                return got
        raise last

    def _scan_hedged(
        self, url: str, body, headers, delay: float, last
    ) -> tuple[bytes | None, Exception | None]:
        """One hedged attempt: primary on a pool thread; if it has not
        answered within ``delay``, race a second worker. First response
        wins; the loser keeps running and is ignored."""
        pool = self._hedge_pool()
        started = threading.Event()

        def primary():
            # stamps actual start: under a saturated pool the submit
            # may queue, and a queued primary must not trigger a hedge
            # (the delay would measure queue wait, not the worker, and
            # the hedge would pile more load onto the same full pool)
            started.set()
            return self._scan_once(url, body, headers)

        futs = {pool.submit(primary): url}
        done, _pending = futures_mod.wait(futs, timeout=delay)
        if not done and started.is_set():
            other = self._pick_other(url)
            if other is not None:
                self._note_hedge(url, other)
                futs[
                    pool.submit(self._scan_once, other, body, headers)
                ] = other
        pending = set(futs)
        while pending:
            done, pending = futures_mod.wait(
                pending, return_when=futures_mod.FIRST_COMPLETED
            )
            for f in done:
                u = futs[f]
                try:
                    status, out = f.result()
                except Exception as e:
                    last = WorkerError(f"{u}: {e}")
                    self._mark_dead(u)
                    continue
                got, last = self._settle(u, status, out, last)
                if got is not None:
                    if u != url:  # the hedge beat the primary
                        with self._lock:
                            self._hedge_wins += 1
                        publish_event(
                            "scan.hedge_won", winner=u, primary=url
                        )
                    return got, last
        return None, last

    def scan(self, payload: SliceScanPayload):
        """One slice scan on some worker -> VariantIndexShard."""
        from ..index.columnar import loads_index

        return loads_index(self.scan_blob(payload))

    #: reload is a tiny control message — never let it inherit the
    #: (possibly minutes-long) slice-scan timeout
    RELOAD_TIMEOUT_S = 10.0

    def reload_workers(self, *, post=None) -> int:
        """Best-effort concurrent POST /reload to every worker
        (shared-storage fleets re-pin freshly ingested shards without a
        restart); returns how many workers acknowledged. Concurrent with
        a short timeout so one wedged worker cannot stall ingest
        completion, and non-200 answers (404 = reload_fn not wired,
        500 = reload failed) are logged — a fleet silently serving stale
        shards is exactly the failure this call exists to prevent.

        Outcomes feed the scan breaker: any HTTP answer proves the
        worker reachable again (revival after a cooldown — e.g. an
        operator fixed a bad token), except 401/403 which re-confirm
        the auth failure; a transport error keeps/opens the circuit."""
        headers = self._auth_headers()
        if post is None:
            post = (
                self.transport.post_json
                if self.transport is not None
                else urllib_post
            )

        def one(url: str) -> bool:
            try:
                status, doc = post(
                    f"{url}/reload", {}, self.RELOAD_TIMEOUT_S, headers
                )
            except Exception:
                log.warning("worker %s reload failed", url, exc_info=True)
                self._mark_dead(url)
                return False
            if status in (401, 403):
                self._mark_dead(url)
            else:
                self.breaker.record_success(url)
            if status != 200:
                log.warning(
                    "worker %s reload answered http %s: %s",
                    url,
                    status,
                    doc,
                )
                return False
            return True

        with ThreadPoolExecutor(min(8, len(self.worker_urls))) as pool:
            ok = sum(pool.map(one, self.worker_urls))
        if ok < len(self.worker_urls):
            log.warning(
                "only %d/%d workers reloaded; the others serve stale "
                "shards until their next reload/restart",
                ok,
                len(self.worker_urls),
            )
        return ok


class WorkerError(RuntimeError):
    pass


class FleetView:
    """Fleet-wide telemetry federation (ISSUE 12): the coordinator's
    collected view of every worker's ``/ops/digest``, served at
    ``/fleet/status``. Digests are polled lazily at a bounded cadence —
    a ``snapshot()`` older than ``interval_s`` refreshes inline, so an
    unqueried fleet pays nothing and a dashboard polling every second
    still only touches workers once per interval (the low-cadence
    poller the rediscovery loop's shape suggested, without another
    standing thread). Polls ride the engine's authenticated transport:
    the digest exchange lives inside the existing worker-token
    boundary, widening nothing.

    The fleet-level ``diagnosis`` names the **stalest replica** (most
    fingerprint-losing dataset copies by the freshness heuristic, else
    the deepest standing delta tail), the **hottest worker** (highest
    median RTT from the router's own measurements), the **divergent
    datasets** (replicas advertising different copies), and the
    unreachable workers — the federated signal layer live migration
    (``migration.py``) rides on.
    """

    #: per-digest GET budget: a digest is a small control message and
    #: must never inherit the minutes-long search timeout
    DIGEST_TIMEOUT_S = 5.0

    def __init__(self, engine, *, interval_s: float = 10.0,
                 clock=time.monotonic):
        self.engine = engine
        self.interval_s = max(0.5, float(interval_s))
        self._clock = clock
        self._lock = threading.Lock()
        # single-flight refresh: concurrent stale snapshot() calls must
        # not each run a full worker sweep (non-blocking acquire — the
        # loser serves the cached view the winner is refreshing)
        self._poll_lock = threading.Lock()
        # url -> {"digest": dict|None, "error": str|None, "tMono": t}
        self._digests: dict[str, dict] = {}
        self._polls = 0
        self._last_poll: float | None = None

    def _poll_one(self, url: str) -> tuple[str, dict, bool]:
        t = self._clock()
        try:
            status, doc = self.engine._get_auth(
                f"{url}/ops/digest",
                min(self.DIGEST_TIMEOUT_S, self.engine.timeout_s),
            )
        except Exception as e:
            return (
                url,
                {
                    "digest": None,
                    "error": f"{type(e).__name__}: {e}",
                    "tMono": t,
                },
                False,
            )
        if status == 200 and isinstance(doc, dict):
            return url, {"digest": doc, "error": None, "tMono": t}, True
        return (
            url,
            {"digest": None, "error": f"http {status}", "tMono": t},
            False,
        )

    def poll(self) -> int:
        """One collection pass over every configured worker; returns
        how many answered. Workers are swept CONCURRENTLY so the pass
        is bounded by one digest timeout, not N of them — /fleet/status
        bypasses admission and deadlines, so an inline refresh stalling
        ~5 s per dead worker sequentially would be exactly the probe
        hang the bypass exists to avoid. Failures are recorded per
        worker (an unreachable worker is a fleet-status FINDING, not an
        error)."""
        urls = list(self.engine.worker_urls)
        ok = 0
        if urls:
            with ThreadPoolExecutor(
                min(8, len(urls)), thread_name_prefix="fleet-digest"
            ) as pool:
                results = list(pool.map(self._poll_one, urls))
            with self._lock:
                for url, entry, answered in results:
                    self._digests[url] = entry
                    ok += int(answered)
        with self._lock:
            self._polls += 1
            self._last_poll = self._clock()
            for u in list(self._digests):
                if u not in urls:  # decommissioned mid-flight
                    del self._digests[u]
        return ok

    def _divergence(self, rows: dict) -> tuple[dict, dict]:
        """({dataset: {url: fp}} for divergent datasets,
        {url: stale-copy count}) over the cached digests."""
        by_ds: dict[str, dict[str, str]] = {}
        for url, e in rows.items():
            d = e.get("digest")
            if not d:
                continue
            for ds, fp in (d.get("datasetFingerprints") or {}).items():
                by_ds.setdefault(ds, {})[url] = fp
        divergent: dict[str, dict[str, str]] = {}
        stale_counts: dict[str, int] = {}
        for ds, fps in sorted(by_ds.items()):
            if len(set(fps.values())) <= 1:
                continue
            divergent[ds] = dict(sorted(fps.items()))
            win = max(
                fps.values(),
                key=lambda fp: (_fingerprint_freshness(fp), fp),
            )
            for url, fp in fps.items():
                if fp != win:
                    stale_counts[url] = stale_counts.get(url, 0) + 1
        return divergent, stale_counts

    def stats(self) -> dict:
        """The ``fleet.*`` metric values — cached state only, a
        /metrics scrape must never trigger worker network IO."""
        with self._lock:
            rows = {u: dict(e) for u, e in self._digests.items()}
            polls = self._polls
        divergent, _stale = self._divergence(rows)
        return {
            "polls": polls,
            "reachable": sum(
                1 for e in rows.values() if e.get("digest") is not None
            ),
            "divergent": len(divergent),
        }

    def snapshot(self) -> dict:
        """The ``/fleet/status`` document (refreshes inline when the
        cached digests are older than ``interval_s``)."""
        with self._lock:
            last = self._last_poll
        if last is None or self._clock() - last >= self.interval_s:
            # single-flight: only one caller refreshes; a concurrent
            # snapshot serves the cached view instead of doubling the
            # worker sweep
            if self._poll_lock.acquire(blocking=False):
                try:
                    self.poll()
                except Exception:  # a broken poll must not 500 status
                    log.exception("fleet digest poll failed")
                finally:
                    self._poll_lock.release()
        with self._lock:
            rows = {u: dict(e) for u, e in self._digests.items()}
            polls = self._polls
            last = self._last_poll
        now = self._clock()
        divergent, stale_counts = self._divergence(rows)
        workers: dict[str, dict] = {}
        tail_rows: dict[str, int] = {}
        for url in sorted(rows):
            e = rows[url]
            d = e.get("digest")
            w: dict = {
                "reachable": d is not None,
                "ageS": round(now - e["tMono"], 1),
                "medianRttMs": self.engine.router.median_rtt_ms(url),
                "staleDatasets": stale_counts.get(url, 0),
            }
            if d is not None:
                w["digest"] = d
                w["deltaTailRows"] = sum(
                    int(t.get("rows", 0))
                    for t in (d.get("deltaTails") or {}).values()
                )
                tail_rows[url] = w["deltaTailRows"]
            else:
                w["error"] = e.get("error")
            workers[url] = w
        # stalest replica: fingerprint-divergence losers first (the
        # replica serving outdated copies), else the deepest standing
        # delta tail (furthest behind its own compaction)
        stalest = None
        if stale_counts:
            stalest = max(
                sorted(stale_counts), key=lambda u: stale_counts[u]
            )
        elif any(tail_rows.values()):
            stalest = max(sorted(tail_rows), key=lambda u: tail_rows[u])
        rtts = {
            u: w["medianRttMs"]
            for u, w in workers.items()
            if w.get("medianRttMs") is not None
        }
        # worst-compiling replica: the digest's midRequestCompiles field
        # (a replica silently recompiling per request burns its latency
        # budget on XLA, not on serving — name it fleet-wide)
        compiles = {
            u: int((w.get("digest") or {}).get("midRequestCompiles", 0))
            for u, w in workers.items()
        }
        worst_compiling = None
        if any(compiles.values()):
            worst_compiling = max(
                sorted(compiles), key=lambda u: compiles[u]
            )
        # live migrations ride the digest (ISSUE 16): phase + ages per
        # in-flight migration, and the diagnosis names a STUCK one
        # (phase age beyond the controller's stuck bound — the
        # stalest-replica pattern applied to protocol progress)
        migrations: list[dict] = []
        stuck = None
        ctl = getattr(self.engine, "migrations", None)
        if ctl is not None:
            migrations = ctl.status()
            stuck = ctl.stuck()
        return {
            "intervalS": self.interval_s,
            "polls": polls,
            "lastPollAgeS": (
                None if last is None else round(now - last, 1)
            ),
            "workers": workers,
            "migrations": migrations,
            "diagnosis": {
                "stalestReplica": stalest,
                "hottestWorker": (
                    max(sorted(rtts), key=lambda u: rtts[u])
                    if rtts
                    else None
                ),
                "divergentDatasets": divergent,
                "unreachableWorkers": sorted(
                    u for u, w in workers.items() if not w["reachable"]
                ),
                "stuckMigration": stuck,
                "worstCompilingReplica": worst_compiling,
            },
        }


class DistributedEngine:
    """Coordinator: VariantEngine interface over remote workers (+ an
    optional local engine for locally-resident shards).

    Dataset routing is discovered from each worker's ``/datasets`` and
    refreshed on demand. A dataset served by several workers keeps its
    FULL replica list (fingerprint-checked — only identical copies are
    grouped): a :class:`ReplicaRouter` picks among live replicas by
    power-of-two-choices over recent RTTs, ``search`` fails over to the
    next replica when a worker errors or its circuit is open, and slow
    primaries are hedged by a second replica after the hedge delay
    (``transport.replica_hedge`` / ``hedge_delay_s``). When no replica
    of a dataset is reachable the search degrades to partial results
    (``resilience.partial_results``) instead of failing outright, and a
    background rediscovery loop heals routes without a manual reload —
    the fault tolerance the reference got for free from Lambda invoke
    retries landing on a fresh instance.
    """

    #: background rediscovery cadence once a route failure armed the
    #: healing loop (it exits when every configured worker answers)
    REDISCOVERY_INTERVAL_S = 2.0

    def __init__(
        self,
        worker_urls: list[str],
        *,
        local=None,
        config=None,
        timeout_s: float = 600.0,
        retries: int = 2,
        max_threads: int = 64,
        post=None,
        get=None,
        token: str = "",
        breaker: CircuitBreaker | None = None,
        transport: PooledTransport | None = None,
    ):
        from ..config import BeaconConfig, TransportConfig

        # full VariantEngine interface: the API layer reads engine.config
        self.config = config or (
            local.config if local is not None else BeaconConfig()
        )
        self.worker_urls = list(worker_urls)
        self.local = local
        self.timeout_s = timeout_s
        self.retries = retries
        self.max_threads = max_threads
        tcfg = getattr(self.config, "transport", None) or TransportConfig()
        self.transport_config = tcfg
        # default data plane: the pooled keep-alive transport (one
        # instance per engine — connections die with close()); injected
        # post/get callables take precedence (test seams, gRPC swaps)
        self._owns_transport = False
        if (post is None or get is None) and transport is None:
            transport = PooledTransport.from_config(tcfg)
            self._owns_transport = True
        self.transport = transport
        self._post = post if post is not None else transport.post_json
        self._get = get if get is not None else transport.get_json
        # a bytes-capable transport receives the payload's serialized
        # JSON verbatim (no dict round-trip on the hot path); legacy
        # injected transports keep their dict contract
        self._post_bytes_ok = bool(
            getattr(self._post, "accepts_bytes", False)
        )
        self._short_circuits = 0
        self._sc_lock = threading.Lock()
        # does the (possibly injected) transport accept a 4th headers
        # arg? Decided once here so the per-call path never plays
        # TypeError roulette with a swapped gRPC/DCN transport
        import inspect

        try:
            params = inspect.signature(post).parameters
            self._post_takes_headers = len(params) >= 4 or any(
                p.kind == inspect.Parameter.VAR_POSITIONAL
                or p.kind == inspect.Parameter.VAR_KEYWORD
                for p in params.values()
            )
        except (TypeError, ValueError):  # builtins/C callables
            self._post_takes_headers = True
        # self.config is always resolved by now (explicit > local's >
        # default), so the token fallback must read it — reading the raw
        # `config` param would silently drop a token that arrived via
        # local.config.auth.worker_token
        self._token = token or self.config.auth.worker_token
        # per-worker circuit breaker (reference analogue: the invoke
        # retry/backoff AWS applies per lambda): consecutive /search
        # failures open the route, calls fast-fail instead of eating the
        # full timeout each, and a half-open probe readmits the worker.
        # Injectable for tests (fake clock drives transitions).
        res = getattr(self.config, "resilience", None)
        self.breaker = breaker or CircuitBreaker(
            failure_threshold=getattr(
                res, "breaker_failure_threshold", 5
            ),
            reset_timeout_s=getattr(res, "breaker_reset_s", 30.0),
            half_open_probes=getattr(res, "breaker_half_open_probes", 1),
        )
        self._routes_lock = threading.Lock()
        self._discovered = False  # a discovery pass has published
        self._fingerprints: dict[str, str] = {}
        # per-worker last-known /datasets contribution + who answered
        # the most recent pass (the rediscovery loop's healed signal —
        # retained fingerprints must not masquerade as reachability)
        self._last_seen: dict[str, list[tuple[str, str]]] = {}
        self._reachable: set[str] = set()
        self._retention_warned: set[str] = set()
        # monotonic stamp of the last completed discovery pass — the
        # /debug/status replica-table staleness signal
        self._last_publish_mono: float | None = None
        # replica selection (p2c over RTTs, breaker-aware) owns the
        # dataset -> replica-urls table; every /search routing decision
        # goes through router.pick — never by indexing a routes dict
        # (tools/check_transport_usage.py enforces that statically)
        self.router = ReplicaRouter(self.breaker)
        self._failovers = 0
        self._partials = 0
        self._rediscoveries = 0
        self._closed = threading.Event()
        self._rediscover_thread: threading.Thread | None = None
        self._hedge_exec: ThreadPoolExecutor | None = None
        # persistent scatter pool (no per-search thread churn)
        self._pool = ThreadPoolExecutor(
            max_workers=max_threads, thread_name_prefix="dispatch"
        )
        # fleet telemetry federation (ISSUE 12): worker /ops/digest
        # collection + the /fleet/status rollup. Construction is free —
        # digests are only polled when the view is read (lazily, at
        # most once per interval).
        obs_cfg = getattr(self.config, "observability", None)
        self.fleet = FleetView(
            self,
            interval_s=getattr(obs_cfg, "fleet_digest_interval_s", 10.0),
        )
        # per-worker in-flight /search legs (guarded by _sc_lock): the
        # migration cut-over drains a retired source to zero before
        # the source may drop the dataset — a leg started before the
        # retire must finish against a worker that still has the rows
        self._inflight: dict[str, int] = {}
        # live shard migration (ISSUE 16): copy -> dual-serve ->
        # canary-verify -> cut-over, exposed at /fleet/migrate.
        # Constructed lazily-cheap like the fleet view; import here
        # (not module top) because migration.py never imports dispatch
        # but keeping the one-way edge explicit costs nothing.
        from .migration import MigrationController

        self.migrations = MigrationController(self)

    # headers are passed only when there is something to carry (a
    # configured token, an ambient trace id) AND the transport's
    # signature accepts them — legacy 3-arg injected transports keep
    # working, they just don't propagate the trace header. A token with
    # a 3-arg transport still passes headers (auth is correctness; the
    # loud TypeError beats silently-unauthenticated calls).
    def _post_auth(self, url: str, doc: dict, timeout_s: float):
        headers: dict = {}
        if self._token:
            headers["Authorization"] = f"Bearer {self._token}"
        ctx = current_context()
        if ctx is not None and self._post_takes_headers:
            # every coordinator->worker hop carries the request's trace
            # id so worker-side spans share it (the Dapper propagation
            # the reference's SNS fan-out never had)
            headers[TRACE_HEADER] = ctx.trace_id
        if headers:
            return self._post(url, doc, timeout_s, headers)
        return self._post(url, doc, timeout_s)

    def _get_auth(self, url: str, timeout_s: float):
        if self._token:
            return self._get(
                url, timeout_s, {"Authorization": f"Bearer {self._token}"}
            )
        return self._get(url, timeout_s)

    def warmup(self) -> int:
        """Pre-compile the local engine's kernel programs (remote
        workers warm their own at their server start); returns the
        program count — the coordinator deployment must not be the one
        shape the soak-tail fix skips."""
        warm = getattr(self.local, "warmup", None)
        return warm() if warm else 0

    def register_metrics(self, registry) -> None:
        """Coordinator telemetry: per-worker breaker series, the data
        plane's transport series (connection reuse, RTT histogram,
        hedges) and short-circuit counter, plus the local engine's
        instruments (batcher, response cache, dispatch counters) when
        one is wired."""
        register_breaker_metrics(registry, lambda: self.breaker)
        register_transport_metrics(registry)
        register_dispatch_metrics(registry, self.dispatch_stats)
        reg = getattr(self.local, "register_metrics", None)
        if reg is not None:
            reg(registry)

    @property
    def short_circuits(self) -> int:
        """Boolean fan-outs answered before the full worker drain."""
        with self._sc_lock:
            return self._short_circuits

    def dispatch_stats(self) -> dict:
        """The fan-out counters behind the ``dispatch.*`` / ``routing.*``
        series (register_dispatch_metrics reads through this so a
        swapped engine stays observable)."""
        fleet = self.fleet.stats()
        mig = self.migrations.counters()
        with self._sc_lock:
            return {
                "short_circuits": self._short_circuits,
                "failovers": self._failovers,
                "partial_responses": self._partials,
                "rediscoveries": self._rediscoveries,
                "replicas": self.router.replica_count(),
                "fleet_polls": fleet.get("polls", 0),
                "fleet_reachable": fleet.get("reachable", 0),
                "fleet_divergent": fleet.get("divergent", 0),
                "migration_started": mig.get("started", 0),
                "migration_completed": mig.get("completed", 0),
                "migration_rolled_back": mig.get("rolled_back", 0),
                "migration_bytes_copied": mig.get("bytes_copied", 0),
            }

    def route_table_age_s(self) -> float | None:
        """Seconds since the last completed discovery pass published
        the replica table (None before first discovery) — the
        staleness signal ``/debug/status`` reports."""
        with self._routes_lock:
            t = self._last_publish_mono
        return None if t is None else time.monotonic() - t

    def worker_stats(self) -> dict[str, dict]:
        """Per-worker health rollup for ``/debug/status``: breaker
        state, recent median RTT, and whether the latest discovery
        pass reached it. Local state only — never a worker call."""
        with self._routes_lock:
            reachable = set(self._reachable)
        return {
            url: {
                "state": self.breaker.state(url),
                "medianRttMs": self.router.median_rtt_ms(url),
                "reachable": url in reachable,
            }
            for url in self.worker_urls
        }

    def unavailable_datasets(self) -> list[str]:
        """Datasets in the route table with no live replica (every
        copy's circuit open) — served as partial results until the
        background rediscovery heals a route. Local state only
        (breaker observation), so ``/ready`` can report it without a
        worker round-trip."""
        return sorted(
            ds
            for ds, urls in self.router.table().items()
            if urls and not any(self.router.live(u) for u in urls)
        )

    def close(self) -> None:
        """Release the scatter/hedge pools, stop the rediscovery loop,
        and drop the pooled worker connections (engines are long-lived;
        call this when rebuilding one on config/route changes)."""
        self._closed.set()
        self.migrations.close()
        self._pool.shutdown(wait=False, cancel_futures=True)
        # under _sc_lock, paired with _hedge_pool's closed check: a
        # hedge executor created concurrently with close() must not
        # escape shutdown (its non-daemon threads would outlive the
        # engine and stall interpreter exit)
        with self._sc_lock:
            hedge, self._hedge_exec = self._hedge_exec, None
        if hedge is not None:
            hedge.shutdown(wait=False, cancel_futures=True)
        if self._owns_transport and self.transport is not None:
            self.transport.close()

    def __enter__(self) -> "DistributedEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- discovery ----------------------------------------------------------

    @staticmethod
    def _group_replicas(ds: str, entries: list[tuple[str, str]]) -> tuple:
        """The replica urls for one dataset, grouped by per-dataset
        fingerprint: identical shard copies are interchangeable, and so
        are **tail-superset** copies: same base artifacts,
        delta tails forming a subset chain — a replica mid-rolling-
        ingest (deeper tail) is a FRESHER copy of the same dataset,
        not a divergence loser, and the migration dual-serve window
        (target standing one delta behind the source for an instant)
        rides the same relation. On a real mismatch the newest copy
        wins (row-count freshness, :func:`_fingerprint_freshness`) and
        the stale workers are excluded from this dataset's routes —
        failover to a divergent copy would silently change the answer
        mid-request."""
        by_fp: dict[str, list[str]] = {}
        for url, fp in entries:
            by_fp.setdefault(fp, []).append(url)
        if len(by_fp) == 1:
            return tuple(next(iter(by_fp.values())))
        parts = {fp: _fingerprint_parts(fp) for fp in by_fp}
        if all(p is not None for p in parts.values()):
            bases = {p[0] for p in parts.values()}
            tails = sorted(
                (p[1] for p in parts.values()), key=len
            )
            chain = all(
                a <= b for a, b in zip(tails, tails[1:])
            )
            if len(bases) == 1 and chain:
                # every copy is routable; deepest tail first so the
                # back-compat primary view (routes()[ds] = urls[0])
                # points at the freshest copy
                ordered = sorted(
                    by_fp,
                    key=lambda fp: (_fingerprint_freshness(fp), fp),
                    reverse=True,
                )
                publish_event(
                    "routing.tail_superset",
                    dataset=ds,
                    copies=len(by_fp),
                    replicas=sum(len(u) for u in by_fp.values()),
                )
                return tuple(
                    u for fp in ordered for u in sorted(by_fp[fp])
                )
        win = max(by_fp, key=lambda fp: (_fingerprint_freshness(fp), fp))
        losers = sorted(
            u for fp, urls in by_fp.items() if fp != win for u in urls
        )
        log.warning(
            "dataset %s: divergent index copies across workers — routing "
            "to the newest copy on %s, excluding stale %s (re-ingest or "
            "POST /reload the excluded workers)",
            ds,
            sorted(by_fp[win]),
            losers,
        )
        return tuple(by_fp[win])

    def _discover(self) -> dict[str, tuple[str, ...]]:
        found: dict[str, list[tuple[str, str]]] = {}  # url -> [(ds, fp)]
        fps: dict[str, str] = {}
        for url in self.worker_urls:
            try:
                status, doc = self._get_auth(f"{url}/datasets", self.timeout_s)
            except urllib.error.HTTPError as e:
                if e.code in (401, 403):
                    # auth failure must not masquerade as a network
                    # problem: an operator chasing 'unreachable' would
                    # debug routing, not the token
                    log.error(
                        "worker %s rejected coordinator credentials "
                        "(http %s): check BEACON_WORKER_TOKEN / --token",
                        url,
                        e.code,
                    )
                else:
                    log.warning("worker %s unreachable: %s", url, e)
                continue
            except Exception as e:
                log.warning("worker %s unreachable: %s", url, e)
                continue
            if status in (401, 403):
                log.error(
                    "worker %s rejected coordinator credentials (http %s): "
                    "check BEACON_WORKER_TOKEN / --token",
                    url,
                    status,
                )
                continue
            if status != 200:
                continue
            fps[url] = doc.get("fingerprint", "")
            # answering discovery REVIVES an open/half-open route (the
            # rediscovery loop's whole point; like reload_workers'
            # answered -> record_success revival) — but must NOT touch
            # a CLOSED circuit's failure count: /datasets answering
            # says nothing about /search health, and resetting the
            # count every pass would keep a search-broken worker's
            # breaker from ever opening
            if self.breaker.state(url) != CLOSED:
                self.breaker.record_success(url)
            ds_fps = doc.get("dataset_fingerprints") or {}
            found[url] = [
                (ds, str(ds_fps.get(ds, fps[url])))
                for ds in doc.get("datasets", [])
            ]
        with self._routes_lock:
            # per-worker retention: a worker that ANSWERED owns its
            # route contribution outright (dropping a dataset it no
            # longer advertises is correct); a worker that did NOT
            # answer keeps its last-known-good contribution — a
            # partially-successful pass must not silently vanish a
            # dead worker's datasets from the table (they must keep
            # degrading to marked partial results, not to unmarked
            # empty answers)
            merged: dict[str, list[tuple[str, str]]] = {}
            for url in self.worker_urls:
                per = found.get(url)
                if per is None:
                    per = self._last_seen.get(url, [])
                    # warn ONCE per outage, not once per rediscovery
                    # pass (a decommissioned URL left in worker_urls
                    # would otherwise spam this line forever)
                    if per and url not in self._retention_warned:
                        self._retention_warned.add(url)
                        log.warning(
                            "worker %s unreachable during discovery; "
                            "keeping its last-known-good routes "
                            "(%d dataset(s), may be stale) until it "
                            "answers",
                            url,
                            len(per),
                        )
                else:
                    self._retention_warned.discard(url)
                for ds, fp in per:
                    merged.setdefault(ds, []).append((url, fp))
            table = {
                ds: self._group_replicas(ds, entries)
                for ds, entries in merged.items()
            }
            self._discovered = True
            self._last_seen.update(found)
            self._reachable = set(found)
            # last-known fingerprints are retained for unreachable
            # workers too: the aggregate index identity (cache keys)
            # must not flap with reachability
            self._fingerprints.update(fps)
            self._last_publish_mono = time.monotonic()
            self.router.publish(table)
        # the router's view, not the locally computed table: publish()
        # filters migration cut-over pins inside its critical section,
        # and callers must never see a retired route resurrected
        return self.router.table()

    def replica_table(
        self, refresh: bool = False
    ) -> dict[str, tuple[str, ...]]:
        """dataset -> replica urls, discovering on first use."""
        with self._routes_lock:
            discovered = self._discovered
        if not discovered or refresh:
            return self._discover()
        return self.router.table()

    def routes(self, refresh: bool = False) -> dict[str, str]:
        """dataset -> primary worker url (back-compat view of the
        replica table; routing decisions go through the router)."""
        return {
            ds: urls[0]
            for ds, urls in self.replica_table(refresh).items()
            if urls
        }

    # -- fleet membership (the migration grow/shrink seam) -------------------

    def add_worker(self, url: str) -> bool:
        """Admit ``url`` to the fleet and run a discovery pass so its
        datasets enter the routing table (the migration dual-serve
        publish). Returns False when already a member."""
        with self._routes_lock:
            if url in self.worker_urls:
                return False
            self.worker_urls.append(url)
        # discovery takes _routes_lock itself — must run outside it
        self._discover()
        return True

    def remove_worker(self, url: str) -> bool:
        """Drop ``url`` from the fleet and republish routes without
        its contribution (its last-known-good retention included)."""
        with self._routes_lock:
            if url not in self.worker_urls:
                return False
            self.worker_urls.remove(url)
            self._last_seen.pop(url, None)
            self._fingerprints.pop(url, None)
            self._reachable.discard(url)
            self._retention_warned.discard(url)
        self._discover()
        return True

    def inflight(self, url: str) -> int:
        """In-flight /search legs against ``url`` right now — the
        cut-over drain signal (a retired source must answer its
        started legs before it may drop the dataset)."""
        with self._sc_lock:
            return self._inflight.get(url, 0)

    # -- background rediscovery --------------------------------------------

    def _nudge_rediscovery(self) -> None:
        """Arm the healing loop (worker failure / breaker-open saw a
        dead route): one daemon thread re-runs discovery until every
        configured worker answers again, so routes heal without a
        manual reload_workers. Idempotent while a loop is running."""
        if self._closed.is_set():
            return
        with self._routes_lock:
            t = self._rediscover_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(
                target=self._rediscover_loop,
                daemon=True,
                name="dispatch-rediscovery",
            )
            self._rediscover_thread = t
        t.start()

    def _rediscover_loop(self) -> None:
        delay = self.REDISCOVERY_INTERVAL_S
        while not self._closed.wait(delay):
            # a permanently-gone worker (decommissioned URL still in
            # worker_urls) must not spin full-rate discovery forever:
            # back off toward a slow steady probe
            delay = min(delay * 2, max(30.0, self.REDISCOVERY_INTERVAL_S))
            try:
                self._discover()
            except Exception:
                log.exception("route rediscovery pass failed")
            with self._sc_lock:
                self._rediscoveries += 1
            with self._routes_lock:
                # healed = every configured worker ANSWERED the latest
                # pass (not merely has a retained fingerprint from
                # before it died)
                reachable = len(self._reachable)
                healed = all(
                    url in self._reachable for url in self.worker_urls
                )
            publish_event(
                "routing.rediscovery",
                healed=healed,
                reachable=reachable,
                workers=len(self.worker_urls),
            )
            if healed:
                return

    def datasets(self) -> list[str]:
        out = set(self.routes())
        if self.local is not None:
            out |= set(self.local.datasets())
        return sorted(out)

    def index_fingerprint(self) -> str:
        self.routes()
        with self._routes_lock:
            parts = [
                f"{url}={fp}"
                for url, fp in sorted(self._fingerprints.items())
            ]
        if self.local is not None:
            parts.append(f"local={self.local.index_fingerprint()}")
        return "&&".join(parts)

    # -- query path ---------------------------------------------------------

    def _call_worker(
        self, url: str, payload: VariantQueryPayload, deadline=None,
        ctx=None,
    ):
        # the request context rides in explicitly like the deadline
        # (pool thread: the submitting request's thread-locals are not
        # visible) and is re-installed so the trace header and outcome
        # notes work from here down
        with request_context(ctx if ctx is not None else current_context()):
            return self._call_worker_traced(url, payload, deadline)

    def call_replica(
        self, url: str, payload: VariantQueryPayload
    ) -> list[VariantSearchResponse]:
        """One direct ``/search`` against a SPECIFIC replica — no
        failover, no hedging, no routing. The canary prober's
        per-replica probe seam (canary.py): the whole point is to
        exercise exactly one copy and judge its answer, which the
        routed paths' fault tolerance would mask. Probe RTTs do NOT
        feed the router's rings: sub-millisecond boolean probes would
        otherwise dominate the p2c comparison and drag the adaptive
        hedge p95 to probe scale on an idle fleet — every real query
        would then hedge immediately when traffic resumes."""
        return self._call_worker_traced(url, payload, note_rtt=False)

    def _call_worker_traced(
        self, url: str, payload: VariantQueryPayload, deadline=None,
        *, note_rtt: bool = True,
    ):
        # in-flight leg accounting brackets the WHOLE leg (retries
        # included): the migration cut-over drains inflight(url) to
        # zero before the retired source may drop the dataset
        with self._sc_lock:
            self._inflight[url] = self._inflight.get(url, 0) + 1
        try:
            return self._call_worker_leg(
                url, payload, deadline, note_rtt=note_rtt
            )
        finally:
            with self._sc_lock:
                n = self._inflight.get(url, 0) - 1
                if n <= 0:
                    self._inflight.pop(url, None)
                else:
                    self._inflight[url] = n

    def _call_worker_leg(
        self, url: str, payload: VariantQueryPayload, deadline=None,
        *, note_rtt: bool = True,
    ):
        if not self.breaker.allow(url):
            # fast-fail: the route failed repeatedly and its reset
            # window hasn't lapsed — don't spend timeout_s finding out.
            # An open route also arms the background rediscovery loop
            # (the worker may have restarted with fresh shards).
            annotate(breaker="open")
            plan_stage(
                "worker",
                decision="fast_fail",
                reason="breaker_open",
                worker=url,
            )
            self._nudge_rediscovery()
            raise CircuitOpen(f"worker {url}: circuit open")
        # serialize ONCE: the pooled transport ships these bytes
        # verbatim (the old path built a dict just for the transport to
        # re-dumps it); injected dict-contract transports still get one
        doc = (
            payload.dumps().encode()
            if self._post_bytes_ok
            else json.loads(payload.dumps())
        )
        # the request deadline is passed EXPLICITLY by search(): this
        # runs on a pool thread, where the submitting request's
        # thread-local scope is not visible
        if deadline is None:
            deadline = current_deadline()
        last = None
        # one span per worker leg (its own root tree on this pool
        # thread, tied to the request by trace id): on success the
        # worker's side-channel span summary grafts in as child spans,
        # so /_trace?trace_id= shows the coordinator->worker waterfall
        # with network time separated from worker-stage time
        with span("dispatch.worker_call", url=url) as wsp:
            for attempt in range(self.retries + 1):
                timeout_s = deadline.clamp(self.timeout_s)
                if timeout_s is not None and timeout_s <= 0:
                    deadline.check(f"worker {url} call")
                t0 = time.perf_counter()
                try:
                    fault_point("worker.http", url)
                    status, out = self._post_auth(
                        f"{url}/search", doc, timeout_s
                    )
                except Exception as e:
                    last = WorkerError(f"{url}: {e}")
                else:
                    if status == 200:
                        # successful RTTs feed the router's p2c
                        # comparison and the adaptive replica-hedge
                        # delay — and the request's cost vector: the
                        # worker was occupied that long on this
                        # request's behalf (ISSUE 11)
                        rtt_s = time.perf_counter() - t0
                        if note_rtt:
                            self.router.note_rtt(url, rtt_s)
                        charge_cost(worker_rtt_ms=rtt_s * 1e3)
                        self.breaker.record_success(url)
                        _graft_worker_spans(
                            wsp, url, out.get("meta"), rtt_s
                        )
                        return [
                            VariantSearchResponse(**r)
                            for r in out.get("responses", [])
                        ]
                    last = WorkerError(
                        f"{url}: http {status}: {out.get('error')}"
                    )
                if attempt < self.retries:  # no dead sleep after final try
                    time.sleep(min(0.05 * (attempt + 1), 1.0))
        if deadline.expired():
            # the REQUEST ran out of time, not the worker out of
            # health: a deadline-clamped timeout must not count against
            # the route (tight-deadline traffic would open the circuit
            # on a perfectly healthy worker and 503 everyone else)
            raise DeadlineExceeded(
                f"worker {url}: request deadline expired"
            ) from last
        self.breaker.record_failure(url)
        self._nudge_rediscovery()
        raise last

    # -- replica hedging + failover ----------------------------------------

    def _hedge_pool(self) -> ThreadPoolExecutor:
        with self._sc_lock:
            if self._hedge_exec is None:
                if self._closed.is_set():
                    # a leg draining through close() must not create an
                    # executor nothing will ever shut down
                    raise WorkerError("engine closed")
                # every multi-replica leg's PRIMARY rides this pool
                # when hedging is armed, so it must never cap fan-out
                # below the scatter pool: size for max_threads
                # primaries plus their hedges (threads spawn lazily —
                # idle fleets never pay for the ceiling). The
                # started-event gate below still stops a queued
                # primary from triggering load-doubling hedges if the
                # pool somehow saturates.
                self._hedge_exec = ThreadPoolExecutor(
                    max_workers=max(8, 2 * self.max_threads),
                    thread_name_prefix="dispatch-hedge",
                )
            return self._hedge_exec

    def _hedge_candidate(
        self, ds_list: list[str], avoid: set[str]
    ) -> str | None:
        """A live replica (other than ``avoid``) serving EVERY dataset
        in the group, fastest-first, or None when the group has no
        common alternative (single-replica fleets never hedge)."""
        common: set[str] | None = None
        for ds in ds_list:
            urls = set(self.router.replicas(ds))
            common = urls if common is None else common & urls
        cands = sorted((common or set()) - avoid)
        live = [u for u in cands if self.router.live(u)]
        if not live:
            return None
        return min(live, key=lambda u: self.router._rtt(u) or 0.0)

    def _call_replicas(
        self, url: str, payload: VariantQueryPayload, deadline, tried: set
    ) -> list[VariantSearchResponse]:
        """One replica-hedged /search leg (Dean & Barroso promoted from
        scan slices to full searches): the primary runs on the hedge
        pool; if it has not answered within the hedge delay, the same
        sub-query races on a second replica and the first success wins.
        /search is an idempotent read, so the loser's duplicate
        execution only costs its CPU — the hedge still only fires once
        the primary actually STARTED (a primary queued behind a full
        pool must not trigger load-doubling hedges), mirroring the
        transport's started/not-started replay discipline. A hedge
        target that also failed is added to ``tried`` so failover does
        not re-try it."""
        delay = None
        if getattr(self.transport_config, "replica_hedge", True):
            delay = self.router.hedge_delay(
                getattr(self.transport_config, "hedge_delay_s", 0.0)
            )
        other = (
            self._hedge_candidate(payload.dataset_ids or [], {url} | tried)
            if delay is not None
            else None
        )
        if delay is None or other is None:
            return self._call_worker_traced(url, payload, deadline)
        pool = self._hedge_pool()
        ctx = current_context()
        started = threading.Event()

        def primary():
            started.set()
            return self._call_worker(url, payload, deadline, ctx)

        futs = {pool.submit(primary): url}
        done, _pending = futures_mod.wait(futs, timeout=delay)
        if not done and started.is_set():
            note_hedge()  # process-wide transport.hedges counter
            annotate(replica_hedge=True)
            plan_stage(
                "worker", decision="hedged", primary=url, hedge=other
            )
            publish_event("dispatch.hedge", primary=url, hedge=other)
            futs[
                pool.submit(self._call_worker, other, payload, deadline, ctx)
            ] = other
        pending = set(futs)
        last: Exception | None = None
        while pending:
            done, pending = futures_mod.wait(
                pending, return_when=futures_mod.FIRST_COMPLETED
            )
            for f in done:
                u = futs[f]
                try:
                    out = f.result()
                except Exception as e:
                    last = e
                    if u != url:
                        tried.add(u)
                    continue
                if u != url:  # the hedge answered first
                    publish_event(
                        "dispatch.hedge_won", winner=u, primary=url
                    )
                return out
        raise last

    def _search_group(
        self, url, ds_list, payload: VariantQueryPayload, deadline, ctx
    ):
        # like _call_worker: the request context rides in explicitly
        # (pool thread) so trace headers and outcome notes keep working
        with request_context(ctx if ctx is not None else current_context()):
            return self._search_group_traced(url, ds_list, payload, deadline)

    def _search_group_traced(
        self, url: str, ds_list: list[str], payload, deadline
    ) -> tuple[list[VariantSearchResponse], list[str], Exception | None]:
        """One scatter leg with automatic failover: the group's primary
        is tried first (hedged); on a worker error or open circuit each
        dataset re-routes to its next untried replica — never the same
        copy twice — until ``resilience.failover_retries`` extra
        replicas have been spent or the replica set is exhausted.
        Returns ``(responses, failed_datasets, first_error)``; only a
        deadline expiry raises (no time left to fail over)."""
        res = getattr(self.config, "resilience", None)
        max_extra = getattr(res, "failover_retries", 2)
        responses: list[VariantSearchResponse] = []
        failed: list[str] = []
        first_err: Exception | None = None
        work = [(url, list(ds_list), {url})]
        while work:
            u, dss, tried = work.pop()
            sub = dataclasses.replace(payload, dataset_ids=dss)
            try:
                responses.extend(
                    self._call_replicas(u, sub, deadline, tried)
                )
                continue
            except DeadlineExceeded:
                raise  # the request is out of time — no failover
            except (WorkerError, CircuitOpen) as e:
                if first_err is None:
                    first_err = e
            if len(tried) > max_extra:
                # primary + max_extra replicas all failed: give these
                # datasets up to the partial-results path
                failed.extend(dss)
                continue
            regroup: dict[str, list[str]] = {}
            for ds in dss:
                nxt = self.router.pick(ds, avoid=tried)
                if nxt is None:
                    failed.append(ds)
                else:
                    regroup.setdefault(nxt, []).append(ds)
            for nu, nds in sorted(regroup.items()):
                with self._sc_lock:
                    self._failovers += 1
                annotate(failover=True)
                plan_stage(
                    "worker",
                    decision="failover",
                    failed=u,
                    to=nu,
                    datasets=len(nds),
                )
                publish_event(
                    "dispatch.failover",
                    failed=u,
                    to=nu,
                    datasets=len(nds),
                )
                work.append((nu, nds, tried | {nu}))
        return responses, failed, first_err

    def search(
        self, payload: VariantQueryPayload
    ) -> list[VariantSearchResponse]:
        with span("dispatch.search") as sp:
            current_deadline().check("dispatch.search")
            table = self.replica_table()
            wanted = payload.dataset_ids or self.datasets()
            local_ds = (
                set(self.local.datasets()) if self.local is not None else set()
            )
            if any(ds not in local_ds and ds not in table for ds in wanted):
                # an explicitly requested dataset may have been ingested
                # after the last discovery: refresh once before treating
                # it as unknown (a stale skip would be indistinguishable
                # from 'no variants found')
                table = self.replica_table(refresh=True)
            by_worker: dict[str, list[str]] = {}
            local_wanted: list[str] = []
            for ds in wanted:
                if ds in local_ds:
                    local_wanted.append(ds)
                elif ds in table:
                    # p2c primary pick; failover inside the group leg
                    # walks the remaining replicas
                    primary = self.router.pick(ds)
                    if primary is not None:
                        by_worker.setdefault(primary, []).append(ds)
                # still-unknown datasets are skipped, like unmatched
                # chromosomes (get_matching_chromosome filter)

            tasks = sorted(by_worker.items())
            # a boolean-granularity fan-out with no resultset detail
            # requested is a logical OR: the first hit anywhere decides
            # the answer, so the rest of the scatter is abandoned.
            # include_datasets != NONE keeps the full drain — the
            # caller asked for per-dataset responses, and engine-level
            # parity with a single engine must hold for them
            # (knob: transport.bool_short_circuit)
            short_circuit_ok = (
                payload.requested_granularity == "boolean"
                and payload.include_datasets == "NONE"
                and getattr(
                    self.transport_config, "bool_short_circuit", True
                )
            )
            short_circuited = False
            responses: list[VariantSearchResponse] = []
            unavailable: list[str] = []
            group_err: Exception | None = None
            deadline = current_deadline()
            ctx = current_context()
            futures: dict = {}
            if tasks:
                futures = {
                    self._pool.submit(
                        self._search_group, url, ds_list, payload,
                        deadline, ctx,
                    ): url
                    for url, ds_list in tasks
                }
            # which tier is serving this query (the slow-query log's
            # dispatch attribution)
            if tasks:
                annotate(dispatch_tier="http")
                plan_stage(
                    "tier", decision="http", worker_groups=len(tasks)
                )
            elif local_wanted:
                annotate(dispatch_tier="local")
                plan_stage("tier", decision="local")
            # the LOCAL shard search runs on this thread CONCURRENTLY
            # with the worker fan-out (it used to wait for the full
            # drain) — the coordinator's own datasets no longer sit
            # behind the slowest worker's RTT
            first_err: BaseException | None = None
            if local_wanted:
                try:
                    responses.extend(
                        self.local.search(
                            dataclasses.replace(
                                payload, dataset_ids=local_wanted
                            )
                        )
                    )
                except Exception as e:
                    # recorded, not raised: the worker futures must
                    # still be drained (stranded tasks starve the pool)
                    first_err = e
            pending = set(futures)
            # hit_seen is order-independent: once ANY leg of a boolean
            # OR reports a hit, the aggregate answer is decided — a
            # sibling's error cannot change it and must not fail the
            # query, whether it arrived before or after the hit
            hit_seen = short_circuit_ok and any(
                r.exists for r in responses
            )
            if not hit_seen:
                # fan-in consumes futures AS COMPLETED (incremental
                # aggregation, a hit can short-circuit) but still
                # settles every one before raising: a fast-failing
                # worker must not strand slow siblings' tasks in the
                # shared pool. The drain is deadline-bounded: on expiry
                # still-running futures are left to finish on the pool
                # (bounded by their own clamped socket timeouts) and
                # the caller gets DeadlineExceeded now.
                while pending:
                    done, pending = futures_mod.wait(
                        pending,
                        timeout=deadline.remaining(),
                        return_when=futures_mod.FIRST_COMPLETED,
                    )
                    if not done:  # deadline expired mid-drain
                        if first_err is None:
                            first_err = DeadlineExceeded(
                                "worker fan-in: deadline exceeded"
                            )
                        break
                    for f in done:
                        try:
                            out, failed, gerr = f.result()
                        except (
                            Exception,
                            futures_mod.CancelledError,
                        ) as e:
                            # CancelledError (close() mid-search) is a
                            # BaseException: it must not abort the drain
                            if first_err is None:
                                first_err = e
                        else:
                            responses.extend(out)
                            if failed:
                                # this group exhausted its replicas for
                                # these datasets: candidate for partial
                                # results, not an immediate failure
                                unavailable.extend(failed)
                                if group_err is None:
                                    group_err = gerr
                            if short_circuit_ok and any(
                                r.exists for r in out
                            ):
                                hit_seen = True
                    if hit_seen:
                        break
            if hit_seen:
                if pending:
                    # abandon the rest of the scatter: queued futures
                    # are cancelled outright, in-flight ones finish on
                    # the pool and are ignored — for a boolean query
                    # their answers cannot change the aggregate. The
                    # counter only ticks when a drain was actually cut
                    # short.
                    for f in pending:
                        f.cancel()
                    short_circuited = True
                    with self._sc_lock:
                        self._short_circuits += 1
                    annotate(short_circuit=True)
            elif first_err is not None:
                # a local-engine error, deadline expiry, or cancelled
                # drain is a real failure — partial results only cover
                # unreachable replicas
                raise first_err
            elif unavailable:
                unavailable = sorted(set(unavailable))
                self._nudge_rediscovery()
                if not getattr(
                    getattr(self.config, "resilience", None),
                    "partial_results",
                    True,
                ):
                    raise group_err or WorkerError(
                        "no reachable replica for dataset(s): "
                        + ", ".join(unavailable)
                    )
                # graceful degradation: answer with the datasets that
                # responded and mark the unreachable ones — the API
                # layer stamps meta.unavailableDatasets + a warning
                # instead of turning one dead fleet corner into a 502
                with self._sc_lock:
                    self._partials += 1
                annotate(unavailable_datasets=tuple(unavailable))
                plan_stage(
                    "fallback",
                    decision="partial",
                    reason="no_replica",
                    datasets=len(unavailable),
                )
                publish_event(
                    "dispatch.partial", datasets=list(unavailable)
                )
                log.warning(
                    "partial results: no reachable replica for %s (%s)",
                    unavailable,
                    group_err,
                )
            responses.sort(key=lambda r: (r.dataset_id, r.vcf_location))
            sp.note(
                workers=len(tasks),
                responses=len(responses),
                short_circuit=short_circuited,
                unavailable=len(unavailable),
            )
        return responses


# -- multi-host compute -------------------------------------------------------


def init_multihost(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """jax.distributed bring-up for one jit program spanning hosts (the
    pod-scale analogue of the reference's 'serverless means arbitrary
    scalability' premise): after this, ``jax.devices()`` spans all hosts
    and ``mesh.make_mesh`` / ``sharded_query`` shard across DCN+ICI."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def main(argv: list[str] | None = None) -> None:
    """``python -m sbeacon_tpu.parallel.dispatch`` — run one worker host:
    load this host's index shards and serve the typed-payload protocol."""
    import argparse

    from ..config import BeaconConfig
    from ..engine import VariantEngine
    from ..ingest import IngestService

    p = argparse.ArgumentParser(description="beacon query worker host")
    # loopback by default: workers serve all genomic data unauthenticated
    # unless --token/BEACON_WORKER_TOKEN is set, so exposure beyond the
    # host must be an explicit choice (--host 0.0.0.0 on a private net)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=5100)
    p.add_argument("--data-root", default=None)
    p.add_argument(
        "--token",
        default=None,
        help="shared bearer token required on /search, /datasets and "
        "/scan (default: BEACON_WORKER_TOKEN env)",
    )
    p.add_argument(
        "--open-scan",
        action="store_true",
        help="serve /scan without a token (DANGEROUS: /scan reads "
        "arbitrary client-supplied locations; only on airtight private "
        "networks)",
    )
    args = p.parse_args(argv)

    config = BeaconConfig.from_env(args.data_root)
    from ..config import enable_persistent_compile_cache
    from ..harness.faults import install_from_env

    try:
        enable_persistent_compile_cache()
    except OSError:
        log.exception("persistent compilation cache unavailable")
    # worker-side chaos: BEACON_FAULT_PLAN arms seeded fault injection
    install_from_env()
    token = args.token if args.token is not None else config.auth.worker_token
    engine = VariantEngine(config)
    service = IngestService(config, engine=engine)
    n = service.load_all()
    # pre-compile every dispatchable program (first requests must not
    # pay cold compiles; near-free on restart with the persistent cache)
    n_warm = engine.warmup()
    worker = WorkerServer(
        engine,
        host=args.host,
        port=args.port,
        token=token,
        open_scan=args.open_scan,
        reload_fn=service.load_all,
    )
    print(
        f"worker serving on {args.host}:{args.port} ({n} shards, "
        f"datasets: {', '.join(engine.datasets()) or 'none'}, "
        f"{n_warm} kernel programs warmed)"
    )
    try:
        worker.server.serve_forever()
    finally:
        worker.server.server_close()


if __name__ == "__main__":  # pragma: no cover
    main()
