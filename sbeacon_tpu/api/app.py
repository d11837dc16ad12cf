"""The Beacon v2 application: one router over the full REST surface.

Replaces the reference's API Gateway resource tree + 13 route lambdas
(reference: api.tf + api-*.tf path parts; lambda/get*/lambda_function.py
dispatchers) with a single in-process route table:

    /  /info  /configuration  /map  /entry_types  /filtering_terms
    /submit                          (POST new, PATCH update)
    /{entity}                        x {datasets, cohorts, individuals,
    /{entity}/filtering_terms           biosamples, runs, analyses}
    /{entity}/{id}
    /{entity}/{id}/{sub}             (cross-entity + scoped g_variants)
    /g_variants  /g_variants/{id}  /g_variants/{id}/{biosamples,individuals}

Every handler returns ``(status_code, body_dict)``; transport (HTTP server,
tests, batch drivers) is external.
"""

from __future__ import annotations

import hmac
import json
import math
import os
import time
from pathlib import Path

from ..accounting import (
    CostAccounting,
    cost_units,
    disabled_snapshot,
    granularity_label,
    query_shape,
)
from ..canary import CanaryProber
from ..config import BeaconConfig, StorageConfig
from ..engine import VariantEngine
from ..ingest import IngestService
from ..ingest.service import VcfLocationError
from ..harness import faults
from ..metadata import MetadataStore, OntologyStore
from ..metadata.memo import register_memo_metrics
from ..metadata.filters import FilterError
from ..plan import (
    PlanStore,
    plan_document,
    plan_note,
    plan_stage,
    register_plan_metrics,
)
from ..query_jobs import AsyncQueryRunner, QueryJobTable
from ..resilience import (
    NO_DEADLINE,
    AdmissionController,
    Deadline,
    ResilienceError,
    deadline_scope,
    register_admission_metrics,
    register_breaker_metrics,
)
from ..shaping import TrafficShaper, requested_granularity
from ..slo import (
    DIAGNOSTIC_ROUTE_LABELS,
    PROBE_BYPASS_PATHS,
    PROBE_HEAD_LABELS,
    SloEngine,
)
from .. import telemetry as telemetry_mod
from ..telemetry import (
    MetricsRegistry,
    RequestContext,
    SlowQueryLog,
    annotate,
    current_context,
    journal,
    register_device_metrics,
    request_context,
    sanitize_trace_id,
    stage_notes,
)
from ..utils import trace as trace_mod
from ..utils.trace import span, stage, tracer
from .envelopes import Envelopes
from .framework import (
    configuration_response,
    entry_types_response,
    info_response,
    map_response,
)
from .requests import BeaconRequest, RequestError, parse_request
from .submit import submit_dataset
from .variants import (
    decode_internal_id,
    resolve_datasets,
    run_variant_search,
)

ENTITY_PATHS = {
    "datasets",
    "cohorts",
    "individuals",
    "biosamples",
    "runs",
    "analyses",
}

_SET_TYPE = {
    "datasets": "dataset",
    "cohorts": "cohort",
    "individuals": "individuals",
    "biosamples": "biosamples",
    "runs": "runs",
    "analyses": "analyses",
    "g_variants": "genomicVariant",
}

# {parent}/{id}/{child} metadata joins: child rows whose ``column`` = id
_CROSS_ENTITY: dict[tuple[str, str], tuple[str, str]] = {
    ("datasets", "individuals"): ("individuals", "_datasetid"),
    ("datasets", "biosamples"): ("biosamples", "_datasetid"),
    ("cohorts", "individuals"): ("individuals", "_cohortid"),
    ("individuals", "biosamples"): ("biosamples", "individualid"),
    ("biosamples", "analyses"): ("analyses", "biosampleid"),
    ("biosamples", "runs"): ("runs", "biosampleid"),
    ("runs", "analyses"): ("analyses", "runid"),
}


def strip_private(doc: dict) -> dict:
    """Drop '_'-prefixed internal fields (reference jsons.dump
    strip_privates=True on every record response)."""
    return {k: v for k, v in doc.items() if not k.startswith("_")}


def _wants_explain(query_params: dict | None) -> bool:
    """``?explain=1`` (or true/yes/on) — the inline plan request."""
    raw = str((query_params or {}).get("explain") or "").lower()
    return raw in ("1", "true", "yes", "on")


def _header(headers: dict | None, name: str) -> str | None:
    """Case-insensitive single-header lookup over a plain dict."""
    name = name.lower()
    for k, v in (headers or {}).items():
        if k.lower() == name:
            return v
    return None




def _authorization_header(headers: dict) -> str:
    return _header(headers, "authorization") or ""


def bearer_token_verifier(token: str):
    """Default auth hook: require ``Authorization: Bearer <token>``.

    Returns a verifier ``(method, path, headers) -> (authorized, reason)``.
    The reference gates ``/submit`` with an AWS_IAM authorizer (reference:
    api.tf:120-149); deployments needing real identity (OIDC, mTLS) pass
    their own callable as ``BeaconApp(auth_verifier=...)``.
    """

    def verify(method: str, path: str, headers: dict) -> tuple[bool, str]:
        got = _authorization_header(headers)
        # constant-time compare (== short-circuits on the first differing
        # byte, leaking token-prefix length via response timing); encoded
        # to bytes because compare_digest raises TypeError on non-ASCII
        # str, which would turn a malformed header into a 500
        if not hmac.compare_digest(
            got.encode(), f"Bearer {token}".encode()
        ):
            return False, "invalid token"
        return True, ""

    return verify


def _start_profiler_server(port: int) -> None:
    """``jax.profiler.start_server``: lets an operator capture a bounded
    profile of the running server on demand (DEPLOYMENT.md). One per
    process; a failure is logged, never fatal."""
    import logging

    try:
        import jax

        jax.profiler.start_server(port)
    except Exception:
        logging.getLogger(__name__).exception(
            "profiler server on port %s not started", port
        )


class BeaconApp:
    def __init__(
        self,
        config: BeaconConfig | None = None,
        *,
        store: MetadataStore | None = None,
        ontology: OntologyStore | None = None,
        engine: VariantEngine | None = None,
        ingest: IngestService | None = None,
        auth_verifier=None,
    ):
        if config is None:
            # configless (ad hoc / test) apps keep sqlite in memory and
            # write index shards under a throwaway temp root, removed when
            # the app is garbage-collected
            import tempfile

            config_given = False
            self._tmp_root = tempfile.TemporaryDirectory(prefix="beacon-")
            self.config = BeaconConfig(
                storage=StorageConfig(root=Path(self._tmp_root.name))
            )
        else:
            config_given = True
            self.config = config
        storage = self.config.storage
        if ontology is None:
            ontology = (
                OntologyStore(storage.ontology_db)
                if config_given
                else OntologyStore()
            )
        self.ontology = ontology
        if store is None:
            store = (
                MetadataStore(storage.metadata_db, ontology=self.ontology)
                if config_given
                else MetadataStore(ontology=self.ontology)
            )
        elif store.ontology is None:
            store.ontology = self.ontology
        self.store = store
        self.engine = engine or VariantEngine(self.config)
        # ingestion always targets an engine that can host shards: a
        # DistributedEngine coordinator exposes its local VariantEngine
        # as .local (shard ownership lives on hosts, not the coordinator)
        ingest_engine = getattr(self.engine, "local", None) or self.engine
        if ingest is None and not hasattr(ingest_engine, "add_index"):
            # fail at wiring time, not as an opaque 500 on first /submit
            raise ValueError(
                "engine cannot host index shards (no add_index): pass a "
                "DistributedEngine with local=VariantEngine(...), or an "
                "explicit ingest= service"
            )
        self.ingest = ingest or IngestService(
            self.config, engine=ingest_engine, store=self.store
        )
        self.env = Envelopes(self.config.info)
        # async query runner over the job table (VariantQueries/
        # VariantQueryResponses roles): coalesces concurrent identical
        # queries and caches results for the query TTL in memory; its
        # writer thread journals finished jobs to the table (oversized
        # response sets spilled to query_results_dir) for a restart
        storage.ensure()
        self.query_jobs = QueryJobTable(
            storage.root / "query-jobs.sqlite",
            spill_dir=storage.query_results_dir,
            inline_limit=self.config.engine.max_response_inline_bytes,
        )
        self.query_runner = AsyncQueryRunner(self.engine, self.query_jobs)
        # resilience envelope (resilience.py): bounded in-flight
        # admission + request deadlines; /health, /ready and /metrics
        # bypass it so probes answer while the server is saturated
        res = self.config.resilience
        self.admission = AdmissionController(
            res.max_in_flight, retry_after_s=res.shed_retry_after_s
        )
        # traffic shaping (shaping.py): tenant-weighted fair queueing +
        # priority lanes in FRONT of the global gate (a queued request
        # holds no admission slot), with the brownout ladder fed by the
        # SLO engine's breach signal below. The hedge kill-switch is
        # process-wide, like the scan pools it governs.
        def _hedge_control(enabled: bool) -> None:
            from ..parallel.dispatch import set_hedging_enabled

            set_hedging_enabled(enabled)

        # cost accounting (accounting.py): every tracked request's
        # CostVector folds into the per-(tenant, lane, query-shape)
        # table served at /ops/costs; tenant cardinality reuses
        # shaping's cap. Built BEFORE the shaper so the cost-aware DRR
        # seam (BEACON_COST_DRR) can charge measured shape costs.
        obs_cfg = self.config.observability
        if getattr(obs_cfg, "cost_accounting", True):
            self.accounting = CostAccounting(
                window_s=getattr(obs_cfg, "cost_window_s", 300.0),
                max_tenants=self.config.shaping.max_tenants,
            )
        else:
            self.accounting = None
        self.shaping = TrafficShaper.from_config(
            self.config,
            hedge_control=_hedge_control,
            cost_charge_fn=(
                self.accounting.drr_charge
                if self.accounting is not None
                else None
            ),
        )
        # the background compactor runs off any request context: book
        # its fold cost under the 'system' tenant via the explicit hook
        compactor = getattr(self.ingest, "compactor", None)
        if compactor is not None and self.accounting is not None:
            compactor.accounting = self.accounting
        # readiness flag: constructed apps are servable; a deployment
        # may clear it during reload/drain so load balancers back off
        self.ready = True
        # telemetry plane (telemetry.py): one typed-metrics registry per
        # app — every producer registers its instruments here and
        # /metrics renders the registry (JSON or Prometheus text)
        # instead of hand-assembling nested dicts
        self.telemetry = MetricsRegistry()
        obs = self.config.observability
        self.slow_log = SlowQueryLog(
            threshold_ms=obs.slow_query_ms, path=obs.slow_query_log
        )
        # SLO engine (slo.py): per-route availability + latency
        # objectives evaluated as 5m/1h burn rates over every request
        # outcome; served at /slo and as slo.* gauges. The brownout
        # ladder subscribes to its breach signal: sustained burn steps
        # degradation up, sustained recovery steps it back down.
        self.slo = SloEngine.from_config(
            obs, max_tenants=self.config.shaping.max_tenants
        )
        self.slo.add_breach_listener(self.shaping.on_slo_signal)
        # execution-plan plane (plan.py): sampled per-request plan
        # documents aggregated by (query-shape, plan-shape) and served
        # at /ops/plans, with the drift sentinel's observation window
        # tied to the canary interval — the prober's round loop rolls
        # the window, so a dominant-shape flip (the mesh stack going
        # stale, L0 coverage collapsing to tail walks) is diagnosed
        # within one canary round even on a coordinator with no
        # organic traffic
        self.plans = PlanStore(
            sample_n=getattr(obs, "plan_sample_n", 16),
            drift_windows=getattr(obs, "plan_drift_windows", 2),
            window_s=getattr(obs, "canary_interval_s", 30.0),
        )
        # known-answer canary prober (canary.py): expected-answer
        # probes derived from the serving snapshot, run per query
        # shape x dispatch path under the synthetic 'canary' route —
        # budget- and cost-excluded like every probe. The thread waits
        # one full interval before its first round.
        self.canary = CanaryProber(
            self.engine,
            interval_s=getattr(obs, "canary_interval_s", 30.0),
            enabled=getattr(obs, "canary_enabled", True),
            latency_ms=getattr(obs, "canary_latency_ms", 1000.0),
            plan_store=self.plans,
        )
        self.canary.start()
        # flight recorder: the process journal was built from env
        # defaults at import; the config tier re-applies here so
        # BEACON_EVENT_JOURNAL_* and explicit
        # ObservabilityConfig fields agree
        journal.configure(
            keep=getattr(obs, "event_journal_size", 1024),
            enabled=getattr(obs, "event_journal", True),
        )
        # device-plane flight recorder (ISSUE 14): same config-tier
        # re-application as the journal — the process global was built
        # from BEACON_DEVICE_RING_SIZE / BEACON_COMPILE_TRACKING env
        # defaults at import. Resolved through the module at call time
        # (never bound by value here), so a test that swaps
        # telemetry.flight_recorder swaps this app's view too.
        telemetry_mod.flight_recorder.configure(
            ring_size=getattr(obs, "device_ring_size", 256),
            compile_tracking=getattr(obs, "compile_tracking", True),
        )
        # the interpreter's collections as the ``gc`` stage, once per
        # process; an operator's on-demand device profile (the stage
        # annotations sit beside the device lines in it)
        trace_mod.install_gc_stage()
        # where the slowest twentieth of a route's requests spent their
        # time, from the stage vector each request carries (ISSUE 37)
        self.tails = trace_mod.TailFold()
        # ... and the interpreter lock's turn as ``runtime.lock_turn``,
        # from one daemon thread that ends with close() and, once a
        # second, takes the fold's thresholds anew
        self.lock_probe = trace_mod.LockTurnProbe(self, self.tails.refresh)
        self.lock_probe.start()
        if obs.profiler_port:
            _start_profiler_server(obs.profiler_port)
        self._register_metrics()
        # mutating-route auth (reference /submit is AWS_IAM-gated,
        # api.tf:120-149): explicit verifier > config token > open (dev)
        if auth_verifier is not None:
            self.auth_verifier = auth_verifier
        elif self.config.auth.submit_token:
            self.auth_verifier = bearer_token_verifier(
                self.config.auth.submit_token
            )
        else:
            self.auth_verifier = None

    def close(self) -> None:
        """Release app-owned resources: the async runner's worker pool
        and writer thread (which stores what is queued first), then the
        job table. The engine is NOT closed here — it may be
        caller-owned and shared (pass-in wiring); call engine.close()
        separately when this app owns it."""
        self.query_runner.close()
        self.query_jobs.close()
        self.canary.close()
        self.lock_probe.close()
        shaper_close = getattr(self.shaping, "close", None)
        if shaper_close is not None:
            shaper_close()
        ingest_close = getattr(self.ingest, "close", None)
        if ingest_close is not None:
            ingest_close()

    # -- telemetry wiring ---------------------------------------------------

    def _register_metrics(self) -> None:
        """Wire every producer's typed instruments into this app's
        registry. Suppliers read through ``self`` so components swapped
        at runtime (tests replace ``app.admission``) stay observable."""
        reg = self.telemetry
        # request-level series owned by the app itself; exemplars link
        # each latency bucket to the trace id of its latest request, so
        # a slow bucket resolves at /_trace?trace_id=...
        self._req_latency = reg.histogram(
            "request.latency_ms",
            "end-to-end request latency per route",
            label="route",
            exemplars=True,
            # the route label set is bounded by _route_label but its
            # legitimate cardinality (entity heads x sub-routes) tops
            # the registry's default 64-value guard — raise the cap
            # instead of collapsing real routes to "other"
            max_label_values=128,
        )
        reg.counter(
            "request.slow_queries",
            "requests recorded by the slow-query log",
            fn=lambda: self.slow_log.count(),
        )
        # the tail's layer map: sums of the classed requests' own stage
        # vectors, read from the fold when a snapshot is served
        series = self.tails.series
        reg.counter(
            "request.tail_count",
            "status-200 tracked requests at or over their route's "
            "running p95 of elapsed_ms",
            fn=lambda: series()["tail_count"],
        )
        reg.counter(
            "request.body_count",
            "status-200 tracked requests between their route's running "
            "p40 and p60 of elapsed_ms",
            fn=lambda: series()["body_count"],
        )
        reg.counter(
            "request.classed_total",
            "status-200 tracked requests the fold saw, classed or not",
            fn=lambda: series()["classed_total"],
        )
        reg.counter(
            "request.tail_ms",
            "milliseconds of the tail's requests by chain stage "
            "(unnamed: elapsed_ms less the chain's sum)",
            label="stage",
            fn=lambda: series()["tail_ms"],
        )
        reg.counter(
            "request.body_ms",
            "milliseconds of the body's requests by chain stage "
            "(unnamed: elapsed_ms less the chain's sum)",
            label="stage",
            fn=lambda: series()["body_ms"],
        )
        reg.counter(
            "request.tail_by_granularity",
            "the tail's requests by requested granularity",
            label="granularity",
            fn=lambda: series()["tail_by_granularity"],
        )
        reg.counter(
            "request.classed_by_granularity",
            "status-200 tracked requests by requested granularity",
            label="granularity",
            fn=lambda: series()["classed_by_granularity"],
        )
        self.slo.register_metrics(reg)
        if self.accounting is not None:
            self.accounting.register_metrics(reg)
        else:
            # catalogue stability: the cost.* series exist (zeros) even
            # with accounting off, like every other optional plane
            CostAccounting().register_metrics(reg)
        reg.counter(
            "events.published",
            "control-plane events published to the flight recorder",
            fn=journal.published,
        )
        if "device.launches" not in reg.names():
            # device-plane flight recorder series (ISSUE 14): the
            # recorder is process-global, so the usual app fallback
            # registration keeps a second app from double-registering
            register_device_metrics(reg)
        reg.counter(
            "runtime.gc_pauses",
            "collections of the interpreter's garbage collector",
            label="generation",
            fn=lambda: {
                str(g): n for g, n in enumerate(trace_mod.gc_pauses)
            },
        )
        reg.counter(
            "runtime.gc_pause_ms",
            "milliseconds every thread stood still for a collection",
            fn=lambda: tracer.stage_counts("gc")[1],
        )
        # what the process's Python threads cost and how often they gave
        # their processor up, by role (utils/trace.THREAD_ROLES), beside
        # the whole process's CPU and the cores it may use: read from
        # /proc when a snapshot is served, one scan for the four
        threads = trace_mod.thread_clock.read
        reg.counter(
            "runtime.thread_cpu_ms",
            "CPU milliseconds of the process's Python threads",
            label="role",
            fn=lambda: threads()["cpu_ms"],
        )
        reg.counter(
            "runtime.thread_yields",
            "voluntary context switches of the Python threads: lock "
            "hand-overs waited for, parked waits, blocking calls",
            label="role",
            fn=lambda: threads()["yields"],
        )
        reg.counter(
            "runtime.thread_preempted",
            "involuntary context switches of the Python threads",
            label="role",
            fn=lambda: threads()["preempted"],
        )
        reg.counter(
            "runtime.process_cpu_ms",
            "CPU milliseconds of the whole process, the runtime's own "
            "threads included",
            fn=lambda: threads()["process_cpu_ms"],
        )
        reg.gauge(
            "runtime.host_cpus",
            "processors the process may run on",
            fn=lambda: len(os.sched_getaffinity(0)),
        )
        reg.gauge(
            "runtime.stage_cpu_every",
            "scopes of a stage to one that reads its thread's CPU clock "
            "(1: every scope; more where the clock is dear)",
            fn=lambda: tracer.cpu_every,
        )
        self.canary.register_metrics(reg)
        register_memo_metrics(reg, lambda: self.store.resolve_memo)
        register_plan_metrics(reg, self.plans)
        register_admission_metrics(reg, lambda: self.admission)
        self.shaping.register_metrics(reg)
        self.query_runner.register_metrics(reg)
        engine_reg = getattr(self.engine, "register_metrics", None)
        if engine_reg is not None:
            engine_reg(reg)
        if "breaker.state" not in reg.names():
            # single-host engines have no worker routes; the series
            # still exist (empty) so the catalogue is deployment-stable
            register_breaker_metrics(
                reg, lambda: getattr(self.engine, "breaker", None)
            )
        if "transport.conn.opened" not in reg.names():
            # same catalogue stability for the data-plane transport +
            # fan-out series: a single-host engine never opens worker
            # connections, but the instruments exist (zeros) so
            # dashboards don't flap with the deployment shape
            from ..parallel.dispatch import register_dispatch_metrics
            from ..parallel.transport import register_transport_metrics

            register_transport_metrics(reg)
            register_dispatch_metrics(
                reg,
                lambda: getattr(self.engine, "dispatch_stats", dict)(),
            )
        if "ingest.delta_publishes" not in reg.names():
            # local-less coordinators have no delta registry; zeros
            from ..engine import register_delta_metrics

            register_delta_metrics(
                reg,
                lambda: getattr(
                    getattr(self.engine, "local", None) or self.engine,
                    "delta_metrics",
                    dict,
                )(),
            )
        # compaction + slice-disk series (ingest-while-serving plane)
        from ..ingest.pipeline import register_ingest_metrics
        from ..ingest.service import register_compaction_metrics

        register_ingest_metrics(reg)
        register_compaction_metrics(
            reg,
            lambda: getattr(self.ingest, "compaction_metrics", dict)(),
        )

    #: heads of the two-segment diagnostic surfaces (``ops``,
    #: ``debug``, ``fleet``) — derived from the ONE probe-route source
    #: in slo.py (tools/check_probe_routes.py enforces the derivation)
    _DIAG_HEADS = frozenset(
        label.split(".", 1)[0] for label in DIAGNOSTIC_ROUTE_LABELS
    )

    #: bounded route-label set for the latency histogram — unknown
    #: paths collapse to "other" so a URL scanner cannot mint series.
    #: Probe heads derive from slo.PROBE_ROUTE_LABELS, the single
    #: literal source shared with the SLO budget exclusion and the
    #: auth/admission bypass set.
    _ROUTE_HEADS = (
        ENTITY_PATHS
        | {
            "info",
            "configuration",
            "map",
            "entry_types",
            "filtering_terms",
            "schemas",
            "submit",
            "g_variants",
        }
        | {
            label.split(".", 1)[0]
            for label in DIAGNOSTIC_ROUTE_LABELS
        }
        | PROBE_HEAD_LABELS
    )

    def _route_label(self, path: str) -> str:
        parts = [p for p in path.strip("/").split("/") if p]
        if not parts:
            return "info"
        head = parts[0]
        if head not in self._ROUTE_HEADS:
            return "other"
        if len(parts) == 1:
            return head
        if head in self._DIAG_HEADS:
            # diagnostic surfaces: only the KNOWN two-segment paths get
            # named labels — /ops/<anything-else> must collapse like
            # any other unknown path or a scanner mints series
            label = f"{head}.{parts[1]}"
            return (
                label if label in DIAGNOSTIC_ROUTE_LABELS else "other"
            )
        sub = parts[-1]
        if sub in ("filtering_terms", "g_variants", "biosamples",
                   "individuals", "runs", "analyses"):
            return f"{head}.{sub}"
        return f"{head}.id"

    # -- transport-facing entry --------------------------------------------

    def handle(
        self,
        method: str,
        path: str,
        query_params: dict | None = None,
        body: dict | None = None,
        headers: dict | None = None,
    ) -> tuple[int, dict]:
        """One request end to end, under a request context: a trace id
        minted here (or honored from an inbound ``X-Beacon-Trace``
        header) rides every hop — spans, pool hand-offs, worker HTTP
        calls — and returns in the response envelope's ``meta`` next to
        the elapsed time (the reference's VariantQuery start/end/
        elapsedTime columns, with propagated identity)."""
        t0 = time.perf_counter()
        route = self._route_label(path)
        ctx = RequestContext(
            trace_id=sanitize_trace_id(_header(headers, "x-beacon-trace")),
            route=route,
        )
        with request_context(ctx):
            status, payload = self._handle(
                method, path, query_params, body, headers
            )
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        # probes and diagnostic routes stay out of the stages, as out
        # of SLO budgets and the cost fold: a scrape is not a request
        tracked = self.slo.tracked(route)
        if not tracked:
            self._finish(ctx, route, status, payload, elapsed_ms, False)
            return status, payload
        # api.total is what meta.elapsedTimeMs reports: same two reads
        tracer.observe("api.total", elapsed_ms)
        with stage("api.finish"):
            self._finish(ctx, route, status, payload, elapsed_ms, True)
        return status, payload

    def _finish(
        self, ctx, route, status, payload, elapsed_ms, tracked
    ) -> None:
        """What follows the answer: latency histogram, SLO, cost and
        plan folds, slow log, and the envelope's ``meta`` stamps."""
        # the exemplar is passed explicitly: this runs OUTSIDE the
        # request_context scope, so the ambient lookup would miss
        self._req_latency.observe(
            elapsed_ms, label_value=route, exemplar=ctx.trace_id
        )
        tenant = ctx.notes.get("tenant")
        self.slo.record(route, status, elapsed_ms, tenant=tenant)
        # cost accounting: fold this request's CostVector into the
        # (tenant, lane, shape) table. Probe/diagnostic routes are
        # excluded exactly like SLO budgets — a /metrics scrape is not
        # tenant work. Response bytes are measured here (the one place
        # the final payload exists); the serialization is the same one
        # the transport pays, bounded to tracked routes only.
        if self.accounting is not None and tracked:
            cost = ctx.cost
            if isinstance(payload, dict):
                try:
                    cost.add(
                        response_bytes=len(
                            json.dumps(payload, default=str)
                        )
                    )
                except (TypeError, ValueError):
                    pass
            # seal BEFORE snapshotting: late charges (a launch
            # finishing after this request 504ed, a losing hedge leg's
            # RTT) redirect to the unattributed residue, and a charge
            # racing this very fold cannot fall between the snapshot
            # and the seal — it lands in exactly one of the two sides
            cost.seal()
            self.accounting.record(
                tenant or "anon",
                ctx.notes.get("lane") or "interactive",
                query_shape(route, ctx.notes.get("granularity")),
                cost.snapshot(),
            )
        # execution-plan fold: tracked requests' stage trails aggregate
        # by (query-shape, plan-shape) for /ops/plans and the drift
        # sentinel. Probe/diagnostic routes are excluded exactly like
        # SLO budgets and the cost fold — the canary folds its own
        # probes under bounded synthetic shapes instead.
        if tracked:
            self.plans.observe(
                query_shape(route, ctx.notes.get("granularity")),
                ctx.plan,
                units=cost_units(ctx.cost.snapshot()),
                trace_id=ctx.trace_id,
            )
        if tracked and status == 200:
            # the tail's layer map: a request at or over its route's
            # running p95, or in its middle fifth, adds its own stage
            # vector to that side's sums
            self.tails.fold(
                route, elapsed_ms, ctx.stages,
                granularity_label(ctx.notes.get("granularity")),
            )
        notes = ctx.notes
        if ctx.cost.nonzero():
            # slow-query records carry the cost decomposition: a tail
            # is attributable to device time vs host scan vs worker
            # RTT without cross-referencing /ops/costs
            notes = {**notes, "cost": ctx.cost.as_dict()}
        if ctx.plan:
            # ... and the plan fingerprint + any refusal reasons: a
            # slow record says WHICH road the query took (and which it
            # was refused) without a second lookup
            notes = {**notes, "plan": plan_note(ctx)}
        if self.slow_log.records(elapsed_ms):
            # ... and WHERE its time went: the request's own stage
            # vector, and what pool threads did for it beside
            notes = {**notes, **stage_notes(ctx)}
        self.slow_log.maybe_record(
            trace_id=ctx.trace_id,
            route=route,
            status=status,
            elapsed_ms=elapsed_ms,
            notes=notes,
        )
        if isinstance(payload, dict):
            meta = payload.get("meta")
            if isinstance(meta, dict):
                meta["traceId"] = ctx.trace_id
                meta["elapsedTimeMs"] = round(elapsed_ms, 2)
                if ctx.explain:
                    # ?explain=1 (gated in _handle): the full bounded
                    # plan document rides the envelope — never cached,
                    # since explain forces no_response_cache
                    # EXPLAIN with the timings: the stage vector too
                    meta["executionPlan"] = {
                        **plan_document(ctx), **stage_notes(ctx, "Ms"),
                    }
                unavailable = ctx.notes.get("unavailable_datasets")
                if unavailable:
                    # partial-results degradation (dispatch.search):
                    # every replica of these datasets was unreachable,
                    # so the response covers the datasets that
                    # answered — say so instead of 502ing the request
                    meta["unavailableDatasets"] = list(unavailable)
                    meta.setdefault("warnings", []).append(
                        "no reachable replica for dataset(s): "
                        + ", ".join(unavailable)
                        + "; results are partial"
                    )

    def _handle(
        self, method, path, query_params, body, headers
    ) -> tuple[int, dict]:
        try:
            with span("api.handle", path=path, method=method):
                head = path.strip("/")
                if (
                    method.upper() == "GET"
                    and head in PROBE_BYPASS_PATHS
                ):
                    # probes/metrics AND the self-diagnosis surfaces
                    # bypass auth, admission and deadlines: a flight
                    # recorder that stops answering exactly when the
                    # server is saturated or shedding is useless —
                    # answering then is their whole job. The path set
                    # derives from slo.PROBE_ROUTE_LABELS — the SAME
                    # source that excludes these routes from SLO
                    # budgets and the cost fold below.
                    return self._probe(head, query_params, headers)
                denied = self._check_auth(method.upper(), path, headers)
                if denied is not None:
                    return denied
                if _wants_explain(query_params):
                    denied = self._check_explain(headers)
                    if denied is not None:
                        return denied
                    ctx = current_context()
                    if ctx is not None:
                        # armed only after the gate: an unauthorized
                        # ?explain=1 never records, never bypasses the
                        # response cache, never changes the answer
                        ctx.explain = True
                deadline = self._request_deadline(head, headers)
                # traffic shaping: classify tenant (header/API key/anon
                # bucket) and priority lane (interactive boolean-count
                # vs bulk record retrieval), then admit through the
                # weighted fair queue BEFORE the global gate — a queued
                # request holds no admission slot, and the deadline
                # scope wraps the queue wait so it stays bounded
                tenant = self.shaping.tenant_of(headers)
                lane = self.shaping.lane_of(head, query_params, body)
                granularity = requested_granularity(query_params, body)
                annotate(tenant=tenant, lane=lane)
                plan_stage("admission", decision=lane, tenant=tenant)
                if granularity:
                    annotate(granularity=granularity)
                # the query-shape key (route x granularity): the same
                # key the accounting fold uses, so the cost-aware DRR
                # (BEACON_COST_DRR) charges admission with the measured
                # cost of exactly this shape
                ctx = current_context()
                shape = query_shape(
                    ctx.route if ctx is not None else head, granularity
                )
                # only the ENTRY of the two gates is the wait: the stage
                # closes once both are held (or a shed request leaves)
                with stage("api.admit") as admit, deadline_scope(
                    deadline
                ), self.shaping.admit(
                    tenant, lane, shape
                ), self.admission.admit():
                    admit.close()
                    return self._route(
                        method.upper(), path, query_params, body
                    )
        except ResilienceError as e:
            # 429 shed / 503 batch-timeout & circuit-open / 504 deadline
            payload = self.env.error(e.status, str(e))
            if e.retry_after_s is not None:
                # integer seconds, rounded up: the RFC 9110 Retry-After
                # header only carries whole seconds, and the envelope
                # field must say the SAME thing the header does (the
                # transport derives the header from this field) — a
                # sub-second adaptive value still advises >= 1 s
                payload["retryAfterSeconds"] = max(
                    1, math.ceil(e.retry_after_s)
                )
            return e.status, payload
        except TimeoutError as e:
            return 504, self.env.error(504, str(e))
        except (RequestError, FilterError, VcfLocationError) as e:
            return 400, self.env.error(400, str(e))
        except Exception as e:  # pragma: no cover - defensive 500
            return 500, self.env.error(500, f"{type(e).__name__}: {e}")

    def _request_deadline(self, head: str, headers: dict | None) -> Deadline:
        """The request's deadline: ``X-Beacon-Deadline`` header
        (seconds) when sent, else the config default — except for
        ``/submit``, where bulk ingest is a batch job and only an
        explicit header bounds it."""
        raw = _header(headers, "x-beacon-deadline")
        if raw is not None:
            try:
                seconds = float(raw)
                # NaN slips through every <=0 guard (all comparisons
                # false) and would poison downstream clamps with a
                # deadline that is never expired yet has 0 remaining;
                # inf and <=0 are equally meaningless as bounds — and
                # <=0 must NOT silently disable the operator's default
                # (Deadline.after semantics), so all three reject
                if not math.isfinite(seconds) or seconds <= 0:
                    raise ValueError(raw)
                return Deadline.after(seconds)
            except (TypeError, ValueError):
                raise RequestError(
                    f"invalid X-Beacon-Deadline header: {raw!r}"
                    " (want a finite number of seconds > 0)"
                ) from None
        if head == "submit":
            return NO_DEADLINE
        return Deadline.after(self.config.resilience.default_deadline_s)

    def _probe(
        self,
        head: str,
        query_params: dict | None = None,
        headers: dict | None = None,
    ) -> tuple[int, dict]:
        info = self.config.info
        if head == "health":
            # liveness: cheap, no store/engine access
            return 200, {"ok": True, "beaconId": info.beacon_id}
        if head == "ready":
            # readiness: local state only — never a worker round-trip
            # (a probe that can hang is worse than no probe)
            local = getattr(self.engine, "local", None) or self.engine
            body = {
                "ready": bool(self.ready),
                "beaconId": info.beacon_id,
                "shards": len(getattr(local, "_indexes", {})),
                "inFlight": self.admission.metrics()["in_flight"],
            }
            # degraded datasets (every replica's circuit open) are
            # reported but do NOT flip readiness: the server still
            # serves everything else, with partial-results envelopes
            # naming the rest — pulling it from rotation would turn a
            # partial outage into a total one
            degraded = getattr(self.engine, "unavailable_datasets", None)
            if degraded is not None:
                body["degradedDatasets"] = degraded()
            return (200 if self.ready else 503), body
        if head == "slo":
            # per-route objectives + multi-window burn rates (the JSON
            # twin of the slo.* Prometheus gauges); ?tenant=<id> scopes
            # the same document to one tenant's isolated burn rings
            want_tenant = (query_params or {}).get("tenant")
            return 200, self.slo.snapshot(tenant=want_tenant or None)
        if head == "ops/events":
            return self._ops_events(query_params)
        if head == "ops/costs":
            # the tenant accounting plane's rollup: top tenants by
            # cost unit, per-shape mean/p99, attribution ratio
            if self.accounting is None:
                return 200, disabled_snapshot()
            return 200, self.accounting.snapshot()
        if head == "ops/plans":
            # the execution-plan plane's rollup: per (query-shape,
            # plan-shape) counts, cost-unit means, exemplar trace ids
            # (resolvable through /_trace when tracing is on), and the
            # drift sentinel's recent dominant-shape flips
            return 200, self.plans.snapshot()
        if head == "fleet/status":
            # fleet-wide federation rollup: every worker's /ops/digest
            # collected at a bounded cadence + the coordinator's own
            # digest, with a fleet-level diagnosis (stalest replica,
            # hottest worker, divergent fingerprints)
            return 200, self._fleet_status()
        if head == "fleet/migrations":
            # live shard-migration history + in-flight phases: a
            # diagnostic read (the POST trigger is /fleet/migrate,
            # behind the worker-token gate)
            ctl = getattr(self.engine, "migrations", None)
            return 200, {
                "migrations": ctl.status() if ctl is not None else [],
                "counters": (
                    ctl.counters() if ctl is not None else {}
                ),
                "stuck": ctl.stuck() if ctl is not None else None,
            }
        if head == "debug/status":
            return 200, self._debug_status()
        if head == "device/status":
            return 200, self._device_status()
        # /metrics: content negotiation — ?format=openmetrics or an
        # ``Accept: application/openmetrics-text`` (what a modern
        # Prometheus scrape sends first) gets the OpenMetrics dialect
        # WITH exemplar annotations; ?format=prometheus or plain
        # ``Accept: text/plain`` gets the classic text format, whose
        # parsers reject exemplar syntax; everything else the
        # back-compat nested JSON (which always carries the
        # ``exemplars`` maps)
        fmt = (query_params or {}).get("format", "")
        accept = _header(headers, "accept") or ""
        if fmt == "openmetrics" or "application/openmetrics-text" in accept:
            return 200, self.telemetry.render_prometheus(openmetrics=True)
        if fmt == "prometheus" or "text/plain" in accept:
            return 200, self.telemetry.render_prometheus()
        return 200, self._metrics()

    def _ops_events(self, query_params: dict | None) -> tuple[int, dict]:
        """The flight recorder, filtered: ``?since=<seq>`` returns only
        newer events — the OLDEST ``limit`` of them, with a
        ``nextSince`` cursor to pass back as ``since``, so a tailing
        client pages forward through a burst without re-reading or
        silently skipping the middle (ISSUE 12 satellite; previously
        the newest ``limit`` were served and a tailer had to guess the
        resume point). ``?kind=breaker`` filters by kind prefix
        (comma-separated list accepted)."""
        qp = query_params or {}
        try:
            since = int(qp.get("since") or 0)
            limit = int(qp.get("limit") or 256)
        except (TypeError, ValueError):
            return 400, self.env.error(
                400, "since/limit must be integers"
            )
        events, next_since = journal.events_page(
            since=since, kind=str(qp.get("kind") or ""), limit=limit
        )
        return 200, {
            "events": events,
            "nextSince": next_since,
            "lastSeq": journal.last_seq(),
            "published": journal.published(),
            "enabled": journal.enabled,
        }

    def _digest_extras(self) -> dict:
        """The coordinator's app-tier digest fields (the worker digest
        carries engine fields only): SLO breaches, slow-query count,
        top cost tenants, canary rollup."""
        canary = self.canary.counters()
        extras = {
            "sloBreached": self.slo.breached_routes(),
            "slowQueries": self.slow_log.count(),
            "canary": {
                "mismatches": canary["mismatches"],
                "failures": canary["failures"],
            },
        }
        if self.accounting is not None:
            extras["topCostTenants"] = self.accounting.snapshot(
                top_n=3
            )["topTenants"]
        else:
            extras["topCostTenants"] = []
        return extras

    def _fleet_status(self) -> dict:
        """The ``/fleet/status`` document: the FleetView's per-worker
        digest rollup + diagnosis (fan-out engines), always including
        the coordinator's own digest as ``local`` — a single-host
        deployment serves the same schema with an empty worker map."""
        from ..parallel.dispatch import ops_digest

        local_engine = getattr(self.engine, "local", None) or self.engine
        local = ops_digest(local_engine, extras=self._digest_extras())
        fleet = getattr(self.engine, "fleet", None)
        if fleet is None:
            doc = {
                "intervalS": getattr(
                    self.config.observability,
                    "fleet_digest_interval_s",
                    10.0,
                ),
                "polls": 0,
                "lastPollAgeS": None,
                "workers": {},
                "diagnosis": {
                    "stalestReplica": None,
                    "hottestWorker": None,
                    "divergentDatasets": {},
                    "unreachableWorkers": [],
                    "worstCompilingReplica": None,
                },
            }
        else:
            doc = fleet.snapshot()
        doc["local"] = local
        return doc

    def _fleet_migrate(self, body: dict) -> tuple[int, dict]:
        """``POST /fleet/migrate``: launch a live shard migration
        (copy -> dual-serve -> canary-verify -> cut-over) on the
        fan-out engine's controller. 202: the protocol runs on a
        background thread — poll ``GET /fleet/migrations`` for phase
        progress; 409: the request was rejected up front (dataset
        already migrating, migrations disabled, bad endpoints)."""
        from ..parallel.migration import MigrationError

        ctl = getattr(self.engine, "migrations", None)
        if ctl is None:
            return 400, self.env.error(
                400,
                "this deployment has no migration controller "
                "(single-host engine — nothing to migrate between)",
            )
        dataset = str(body.get("dataset") or "")
        source = str(body.get("source") or "")
        target = str(body.get("target") or "")
        if not dataset or not source or not target:
            return 400, self.env.error(
                400, "fleet/migrate needs dataset, source and target"
            )
        try:
            m = ctl.start(dataset, source, target)
        except MigrationError as e:
            return 409, self.env.error(409, str(e))
        return 202, {
            "migrationId": m.id,
            "dataset": m.dataset,
            "source": m.source,
            "target": m.target,
            "phase": m.phase,
        }

    def _debug_status(self) -> dict:
        """The self-diagnosis rollup: SLO state, breaker states,
        replica-table staleness, queue depths, and the queue-wait
        decomposition composed into one document whose ``diagnosis``
        names the stage and worker eating the latency budget. Local
        state only — safe to serve while saturated."""
        engine = self.engine
        local = getattr(engine, "local", None) or engine
        breaker = getattr(engine, "breaker", None)
        breakers = breaker.metrics() if breaker is not None else {}
        routing: dict = {}
        router = getattr(engine, "router", None)
        if router is not None:
            age = engine.route_table_age_s()
            routing = {
                "datasets": len(router.table()),
                "replicas": router.replica_count(),
                "tableAgeS": None if age is None else round(age, 1),
                "unavailableDatasets": engine.unavailable_datasets(),
                "workers": engine.worker_stats(),
            }
        batcher = getattr(local, "_batcher", None)
        occ = batcher.occupancy() if batcher is not None else {}
        queues = {
            "admission": self.admission.metrics(),
            "shaping": self.shaping.debug(),
            "runner": self.query_runner.metrics(),
            "batcher": {
                k: occ[k] for k in ("launcher", "fetcher") if k in occ
            },
        }
        # stage decomposition: runner admission wait first, then the
        # batcher/engine stages (batch wait -> encode -> launch ->
        # device -> fetch -> materialize)
        stages: dict = {
            "admission_wait_ms": self.query_runner.queue_wait_summary()
        }
        st = getattr(local, "stage_timing", None)
        if st is not None:
            stages.update(st())
        # ... and every stage of utils/trace.STAGES under its own name,
        # with count and sums: a reader differences two snapshots
        stages.update(tracer.stage_summary())
        # ingest-while-serving rollup: per-dataset delta-tail depth
        # (rows queryable but not yet folded) + compactor counters —
        # "how stale is the base, and is the fold keeping up" in one
        # glance
        ingest: dict = {}
        delta_stats = getattr(local, "delta_stats", None)
        if delta_stats is not None:
            ingest["deltaTails"] = delta_stats()
        l0_status = getattr(local, "l0_status", None)
        if l0_status is not None:
            # the L0 delta-tail mini-index (ISSUE 15): built/served
            # state next to the tails it covers
            ingest["l0"] = l0_status()
        compactor = getattr(self.ingest, "compactor", None)
        if compactor is not None:
            ingest["compactor"] = compactor.metrics()
        slo = self.slo.snapshot()
        breached = sorted(
            r for r, doc in slo["routes"].items() if doc["breached"]
        )
        # totals enclose other stages: api.total would always win
        stage_p99 = {
            name: q.get("p99", 0.0)
            for name, q in stages.items()
            if isinstance(q, dict) and q
            and trace_mod.STAGES.get(name) != "total"
        }
        slowest_stage = (
            max(stage_p99, key=stage_p99.get)
            if any(stage_p99.values())
            else None
        )
        workers = routing.get("workers") or {}
        rtts = {
            u: w["medianRttMs"]
            for u, w in workers.items()
            if w.get("medianRttMs") is not None
        }
        # cost-accounting rollup + the two attribution diagnoses: an
        # operator staring at a breached SLO sees WHO is burning the
        # budget in the same document that names the breach
        costs = (
            self.accounting.debug()
            if self.accounting is not None
            else {"enabled": False}
        )
        # canary rollup (ISSUE 12): the known-answer prober's state —
        # a mismatch here means the data plane is SILENTLY WRONG, the
        # one failure mode no latency or availability signal shows
        canary = self.canary.status()
        # device-plane rollup (ISSUE 14): launch decomposition +
        # padding waste + the mid-request compile count, so the
        # diagnosis can name a device-side regression (a novel batch
        # shape paying its XLA compile inside a request, or a family
        # whose padding wastes most of its launches) next to the
        # breached SLOs it explains
        recorder = telemetry_mod.flight_recorder
        device = {
            "launches": recorder.launch_summary(),
            "padWaste": recorder.pad_waste_by_family(),
            "midRequestCompiles": recorder.mid_request_compiles(),
            # who owns what: every published key with its owner chip
            # and the bytes placed there
            "placement": getattr(local, "placement_table", list)(),
        }
        last_compile = recorder.last_mid_request_compile()
        # execution-plan rollup: observation/sample counters + the
        # drift sentinel's recent dominant-shape flips, with the
        # diagnosis naming the drifted query shapes next to the
        # breaches and canary mismatches they often explain
        plans = self.plans.counters()
        return {
            "ready": bool(self.ready),
            "beaconId": self.config.info.beacon_id,
            "slo": slo,
            "breakers": breakers,
            "routing": routing,
            "queues": queues,
            "ingest": ingest,
            "stages": stages,
            # how often variant queries resolved from the memo of the
            # metadata generation (metadata/memo.py)
            "filters": {"memo": self.store.resolve_memo.stats()},
            "requests": self.tails.status(),
            # how targets' responses came to be: skipped (answered from
            # the launch's counts), inline, pooled
            "engine": {
                "materialized": dict(getattr(local, "materialized", {})),
            },
            "costs": costs,
            "canary": canary,
            "device": device,
            "plans": plans,
            "events": {
                "lastSeq": journal.last_seq(),
                "published": journal.published(),
            },
            "diagnosis": {
                "breachedSlos": breached,
                "openBreakers": sorted(
                    u
                    for u, d in breakers.items()
                    if d.get("state") != "closed"
                ),
                "slowestStage": slowest_stage,
                "slowestWorker": (
                    max(rtts, key=rtts.get) if rtts else None
                ),
                "costliestTenant": costs.get("costliestTenant"),
                "costliestShape": costs.get("costliestShape"),
                "canaryMismatches": list(canary.get("mismatched", [])),
                "worstPadWaste": recorder.worst_pad_waste(),
                "midRequestCompiles": device["midRequestCompiles"],
                "lastMidRequestCompile": (
                    last_compile["key"] if last_compile else None
                ),
                "planDrift": self.plans.drifted_shapes(),
            },
        }

    def _device_status(self) -> dict:
        """The device-plane flight recorder's read surface (ISSUE 14):
        the launch ring summary (padding waste by family/tier,
        evaluated pairs, per-launch records), the compile cache vs the
        warmup shape set, the HBM plane ledger, and the fused
        stack's state. Every piece is a lock-free snapshot (the
        recorder's own short lock, try-lock on the engine ledger) —
        this surface must answer DURING an in-flight stack rebuild,
        the same discipline as ``/ops/digest``."""
        engine = self.engine
        local = getattr(engine, "local", None) or engine
        doc = telemetry_mod.flight_recorder.snapshot()
        ledger = getattr(local, "plane_ledger", None)
        doc["hbm"] = (
            ledger()
            if callable(ledger)
            else {
                "residentBytes": 0,
                "reservedBytes": 0,
                "reservedTokens": 0,
                "budgetBytes": 0,
                "headroomBytes": 0,
                "stale": False,
            }
        )
        stacks: dict = {}
        fused = getattr(local, "fused_stack_status", None)
        if callable(fused):
            stacks["fused"] = fused()
        doc["stacks"] = stacks
        doc["time"] = time.time()
        return doc

    def _metrics(self) -> dict:
        """Serving observability: the typed-instrument registry rendered
        as nested JSON (``admission``, ``runner``, ``batcher``,
        ``response_cache``, ``engine``, ``request`` under their stable
        keys), plus the two surfaces kept in their historical non-dotted
        shapes — per-worker breaker states and the armed fault plan."""
        out = self.telemetry.render_json()
        breaker = getattr(self.engine, "breaker", None)
        if breaker is not None:
            out["breaker"] = breaker.metrics()
        injector = faults.installed()
        if injector is not None:
            out["faults"] = injector.stats()
        return out

    def _check_explain(self, headers) -> tuple[int, dict] | None:
        """404/401/403 envelope for an unauthorized ``?explain=1``,
        else None (explain may proceed).

        The plan document names internal topology — worker URLs, mesh
        shard counts, HBM headroom — so it rides the WORKER-token trust
        boundary exactly like ``/fleet/migrate``: disabled entirely
        unless ``BEACON_EXPLAIN_ENABLED`` (a 404, indistinguishable
        from the feature not existing), then no credential -> 401,
        wrong credential -> 403. Empty worker token = open (dev mode /
        private network), matching the worker endpoints themselves."""
        if not getattr(
            self.config.observability, "explain_enabled", False
        ):
            return 404, self.env.error(
                404, "explain disabled (set BEACON_EXPLAIN_ENABLED)"
            )
        token = self.config.auth.worker_token
        if not token:
            return None
        got = _authorization_header(headers or {})
        if not got:
            return 401, self.env.error(
                401, "missing Authorization header"
            )
        if not hmac.compare_digest(
            got.encode(), f"Bearer {token}".encode()
        ):
            return 403, self.env.error(
                403, "explain requires the worker token"
            )
        return None

    def _check_auth(self, method, path, headers) -> tuple[int, dict] | None:
        """401/403 envelope for unauthorized mutating requests, else None.

        Only mutating routes (``/submit`` POST/PATCH) are gated — read
        routes stay public, matching the reference API where only the
        submit resource carries the AWS_IAM authorizer. Standard HTTP
        semantics decide the status structurally: no credential presented
        (no Authorization header) -> 401; credential presented but
        rejected by the verifier -> 403.

        ``POST /fleet/migrate`` is the exception: it rides the
        WORKER-token trust boundary (``BEACON_WORKER_TOKEN``), not the
        submit authorizer — triggering a migration drives ``/migrate/*``
        artifact reads and drops across the fleet, so it carries the
        same secret and the same blast radius as direct worker access.
        Empty worker token = open (dev mode / private network), matching
        the worker endpoints themselves."""
        if (
            path.strip("/") == "fleet/migrate"
            and method == "POST"
        ):
            token = self.config.auth.worker_token
            if not token:
                return None
            got = _authorization_header(headers or {})
            if not got:
                return 401, self.env.error(
                    401, "missing Authorization header"
                )
            if not hmac.compare_digest(
                got.encode(), f"Bearer {token}".encode()
            ):
                return 403, self.env.error(
                    403, "fleet/migrate requires the worker token"
                )
            return None
        if self.auth_verifier is None:
            return None
        if path.strip("/") != "submit" or method not in ("POST", "PATCH"):
            return None
        ok, reason = self.auth_verifier(method, path, headers or {})
        if ok:
            return None
        if not _authorization_header(headers or {}):
            return 401, self.env.error(401, "missing Authorization header")
        return 403, self.env.error(403, reason or "forbidden")

    # -- routing ------------------------------------------------------------

    def _route(self, method, path, query_params, body):
        parts = [p for p in path.strip("/").split("/") if p]
        info = self.config.info

        if not parts or parts == ["info"]:
            return 200, info_response(info)
        head = parts[0]
        # NOTE: /health, /ready and /metrics are served in handle()
        # BEFORE auth/admission/deadline — probes must answer while the
        # server sheds; they never reach this router
        if head == "schemas":
            # served per-entity default model schemas (the reference
            # vendors these as shared_resources/schemas/ JSON documents;
            # here /map, /entry_types and returnedSchemas point at THIS
            # beacon's resolvable copies — api/model_schemas.py)
            from .model_schemas import ENTITY_SCHEMAS, schema_url

            if len(parts) == 1:
                return 200, {
                    "entityTypes": sorted(ENTITY_SCHEMAS),
                    "schemas": {
                        e: schema_url(info.uri, e)
                        for e in sorted(ENTITY_SCHEMAS)
                    },
                }
            if len(parts) == 2 and parts[1] in ENTITY_SCHEMAS:
                return 200, ENTITY_SCHEMAS[parts[1]]
            return 404, self.env.error(
                404, f"unknown schema /{'/'.join(parts[1:])}"
            )
        if len(parts) == 1:
            if head == "_trace":
                # debug-only profiling surface; 404s unless tracing is on
                if not tracer.is_enabled:
                    return 404, self.env.error(404, "tracing disabled")
                # recent span trees (structured, trace ids attached) +
                # the aggregate report + the slow-query ring; ?trace_id=
                # filters the trees to one distributed request
                want = (query_params or {}).get("trace_id")
                return 200, {
                    "report": tracer.report(),
                    "traces": tracer.recent_trees(trace_id=want),
                    "slowQueries": self.slow_log.recent(),
                }
            if head == "configuration":
                return 200, configuration_response(info)
            if head == "map":
                return 200, map_response(info)
            if head == "entry_types":
                return 200, entry_types_response(info)
            if head == "filtering_terms":
                req = parse_request(method, query_params, body)
                terms = self.store.filtering_terms(
                    skip=req.skip, limit=req.limit
                )
                return 200, self.env.filtering_terms(
                    terms, skip=req.skip, limit=req.limit
                )
            if head == "submit":
                if method not in ("POST", "PATCH"):
                    return 400, self.env.error(
                        400, "submit accepts POST (new) or PATCH (update)"
                    )
                summary = submit_dataset(
                    self, body or {}, update=(method == "PATCH")
                )
                return 200, summary

        if parts == ["fleet", "migrate"]:
            # the migrate trigger (worker-token gated in _check_auth);
            # /fleet/status and /fleet/migrations are probe reads and
            # never reach this router
            if method != "POST":
                return 405, self.env.error(
                    405, "fleet/migrate accepts POST"
                )
            return self._fleet_migrate(body or {})

        with stage("api.parse"):
            req = parse_request(method, query_params, body)

        if head == "g_variants":
            return self._route_g_variants(parts, req)
        if head in ENTITY_PATHS:
            return self._route_entity(parts, req)
        return 404, self.env.error(404, f"unknown path /{'/'.join(parts)}")

    # -- entity routes -------------------------------------------------------

    def _route_entity(self, parts: list[str], req: BeaconRequest):
        kind = parts[0]
        if len(parts) == 1:
            return self._entity_collection(kind, req)
        if len(parts) == 2:
            if parts[1] == "filtering_terms":
                terms = self.store.filtering_terms(
                    skip=req.skip, limit=req.limit, kinds=[kind]
                )
                return 200, self.env.filtering_terms(
                    terms, skip=req.skip, limit=req.limit
                )
            return self._entity_by_id(kind, parts[1], req)
        if len(parts) == 3:
            entity_id, sub = parts[1], parts[2]
            if sub == "filtering_terms" and kind in ("datasets", "cohorts"):
                terms = self.store.filtering_terms_for_entity(
                    kind, entity_id, skip=req.skip, limit=req.limit
                )
                return 200, self.env.filtering_terms(
                    terms, skip=req.skip, limit=req.limit
                )
            if sub == "g_variants" and kind != "cohorts":
                # cohorts expose no g_variants endpoint (reference api
                # tree: cohort endpoints are {id}/individuals only)
                return self._scoped_g_variants(kind, entity_id, req)
            join = _CROSS_ENTITY.get((kind, sub))
            if join is not None:
                child_kind, column = join
                return self._entity_collection(
                    child_kind,
                    req,
                    extra_where=f"{column} = ?",
                    extra_params=[entity_id],
                )
        return 404, self.env.error(404, f"unknown path /{'/'.join(parts)}")

    def _entity_collection(
        self,
        kind: str,
        req: BeaconRequest,
        *,
        extra_where: str | None = None,
        extra_params: list | None = None,
    ):
        """Granularity switch over the store (reference route_individuals.py
        :86-111 get_bool/count/record_query trio)."""
        if req.granularity == "boolean":
            # streaming existence check — at 1M individuals this is the
            # difference between ~0 ms and a full COUNT scan
            found = self.store.exists(
                kind,
                req.filters,
                extra_where=extra_where,
                extra_params=extra_params,
            )
            return 200, self.env.boolean(exists=found)
        count = self.store.count(
            kind,
            req.filters,
            extra_where=extra_where,
            extra_params=extra_params,
        )
        if req.granularity == "count":
            return 200, self.env.count(exists=count > 0, count=count)
        docs = self.store.fetch(
            kind,
            req.filters,
            skip=req.skip,
            limit=req.limit,
            extra_where=extra_where,
            extra_params=extra_params,
        )
        return 200, self.env.result_sets(
            results=[strip_private(d) for d in docs],
            set_type=_SET_TYPE[kind],
            exists=count > 0,
            total=count,
            skip=req.skip,
            limit=req.limit,
        )

    def _entity_by_id(self, kind: str, entity_id: str, req: BeaconRequest):
        doc = self.store.get_by_id(kind, entity_id)
        results = [strip_private(doc)] if doc else []
        if req.granularity == "boolean":
            return 200, self.env.boolean(exists=bool(doc))
        if req.granularity == "count":
            return 200, self.env.count(exists=bool(doc), count=len(results))
        return 200, self.env.result_sets(
            results=results,
            set_type=_SET_TYPE[kind],
            exists=bool(doc),
            total=len(results),
        )

    # -- variant routes ------------------------------------------------------

    def _route_g_variants(self, parts: list[str], req: BeaconRequest):
        if len(parts) == 1:
            return self._g_variants_collection(req)
        variant_id = parts[1]
        if len(parts) == 2:
            return self._g_variants_by_id(variant_id, req)
        if len(parts) == 3 and parts[2] in ("biosamples", "individuals"):
            return self._g_variants_id_entities(variant_id, parts[2], req)
        return 404, self.env.error(404, f"unknown path /{'/'.join(parts)}")

    def _g_variants_collection(self, req: BeaconRequest):
        """POST/GET /g_variants (reference route_g_variants.py:49-208)."""
        start_min, start_max, end_min, end_max = req.coordinates()
        datasets, samples = resolve_datasets(
            self.store, self.ontology, req.assembly_id, req.filters
        )
        agg = run_variant_search(
            self.engine,
            datasets,
            req,
            start_min=start_min,
            start_max=start_max,
            end_min=end_min,
            end_max=end_max,
            samples_by_dataset=samples,
            runner=self.query_runner,
        )
        return 200, self.env.by_granularity(
            req.granularity,
            exists=agg.exists,
            count=len(agg.variants),
            results=agg.results[req.skip : req.skip + req.limit],
            set_type="genomicVariant",
            skip=req.skip,
            limit=req.limit,
        )

    def _g_variants_by_id(self, variant_id: str, req: BeaconRequest):
        """/g_variants/{id}: decode the internal id back into a point query
        (reference route_g_variants_id.py:71-77); resultsets always ALL."""
        assembly, chrom, pos0, ref, alt = decode_internal_id(variant_id)
        req.assembly_id = assembly
        datasets, samples = resolve_datasets(
            self.store, self.ontology, assembly, req.filters
        )
        agg = run_variant_search(
            self.engine,
            datasets,
            req,
            start_min=pos0 + 1,
            start_max=pos0 + 1,
            end_min=pos0 + 1,
            end_max=pos0 + len(alt) + 1,
            reference_name=chrom,
            reference_bases=ref,
            alternate_bases=alt,
            samples_by_dataset=samples,
            include_resultset_responses="ALL",
            runner=self.query_runner,
        )
        return 200, self.env.by_granularity(
            req.granularity,
            exists=agg.exists,
            count=len(agg.variants),
            results=agg.results,
            set_type="genomicVariant",
        )

    def _g_variants_id_entities(
        self, variant_id: str, sub: str, req: BeaconRequest
    ):
        """/g_variants/{id}/{biosamples,individuals}: find the samples
        carrying the variant, then join to the entity table (reference
        route_g_variants_id_individuals.py get_record_query)."""
        assembly, chrom, pos0, ref, alt = decode_internal_id(variant_id)
        req.assembly_id = assembly
        datasets, _ = resolve_datasets(
            self.store, self.ontology, assembly, req.filters
        )
        # force record granularity internally so sample hits materialise
        inner = BeaconRequest(
            method=req.method,
            granularity="record",
            filters=req.filters,
            assembly_id=assembly,
        )
        agg = run_variant_search(
            self.engine,
            datasets,
            inner,
            start_min=pos0 + 1,
            start_max=pos0 + 1,
            end_min=pos0 + 1,
            end_max=pos0 + len(alt) + 1,
            reference_name=chrom,
            reference_bases=ref,
            alternate_bases=alt,
            include_resultset_responses="ALL",
            runner=self.query_runner,
        )
        docs: list[dict] = []
        for ds_id, names in sorted(agg.sample_names_by_dataset.items()):
            docs.extend(
                self.store.entities_for_samples(
                    sub, ds_id, names, skip=0, limit=1_000_000_000
                )
            )
        count = len(docs)
        return 200, self.env.by_granularity(
            req.granularity,
            exists=count > 0,
            count=count,
            results=[
                strip_private(d)
                for d in docs[req.skip : req.skip + req.limit]
            ],
            set_type=_SET_TYPE[sub],
            skip=req.skip,
            limit=req.limit,
        )

    def _scoped_g_variants(self, kind: str, entity_id: str, req: BeaconRequest):
        """/{entity}/{id}/g_variants — the entity-restricted variant search
        (reference route_individuals_id_g_variants.py etc.): datasets come
        from the entity's analyses join and the search runs in
        selected-samples mode; /datasets/{id}/g_variants restricts by
        dataset id only."""
        start_min, start_max, end_min, end_max = req.coordinates()
        if kind == "datasets":
            datasets, samples = resolve_datasets(
                self.store,
                self.ontology,
                req.assembly_id,
                req.filters,
                dataset_ids=[entity_id],
            )
        else:
            samples = {
                "individuals": self.store.sample_names_for_individual,
                "biosamples": self.store.sample_names_for_biosample,
                "runs": self.store.sample_names_for_run,
                "analyses": self.store.sample_names_for_analysis,
            }[kind](entity_id)
            if not samples:
                return 200, self.env.by_granularity(
                    req.granularity, exists=False, count=0, results=[]
                )
            datasets, _ = resolve_datasets(
                self.store,
                self.ontology,
                req.assembly_id,
                req.filters,
                dataset_ids=sorted(samples),
            )
            datasets = [d for d in datasets if samples.get(d["id"])]
        agg = run_variant_search(
            self.engine,
            datasets,
            req,
            start_min=start_min,
            start_max=start_max,
            end_min=end_min,
            end_max=end_max,
            samples_by_dataset=samples,
            runner=self.query_runner,
        )
        return 200, self.env.by_granularity(
            req.granularity,
            exists=agg.exists,
            count=len(agg.variants),
            results=agg.results[req.skip : req.skip + req.limit],
            set_type="genomicVariant",
            skip=req.skip,
            limit=req.limit,
        )
