"""Typed configuration for the whole framework.

The reference spreads configuration over three tiers — terraform variables,
per-lambda environment variables assembled from shared locals, and in-code
constants (reference: variables.tf:1-54, main.tf:24-63, splitQuery
SPLIT_SIZE=10000, variantutils THREADS=500, main.tf:16-17 data ceilings).
Here the same three semantic groups live in one typed config object; env vars
can still override (``BeaconConfig.from_env``) so deployments keep the same
knob surface.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path

#: THE falsy spellings for boolean env knobs — ``from_env`` and every
#: module that reads a BEACON_* flag directly (telemetry.py's recorder
#: switches) share this one set, so an env value can never mean "off"
#: to one reader and "on" to another
ENV_OFF = ("0", "false", "no", "off")


@dataclasses.dataclass(frozen=True)
class BeaconInfo:
    """Beacon identity — reference: variables.tf + getInfo env block."""

    beacon_id: str = "org.tpu.beacon"
    beacon_name: str = "TPU Native Beacon"
    api_version: str = "v2.0.0"
    environment: str = "dev"
    description: str = "TPU-native GA4GH Beacon v2 implementation"
    version: str = "v2.0"
    welcome_url: str = ""
    alternative_url: str = ""
    org_id: str = "TPU"
    org_name: str = "TPU Beacon"
    org_description: str = ""
    org_address: str = ""
    org_welcome_url: str = ""
    org_contact_url: str = ""
    org_logo_url: str = ""
    default_granularity: str = "boolean"
    uri: str = "http://localhost:5000"


@dataclasses.dataclass(frozen=True)
class StorageConfig:
    """On-disk layout re-homing the reference's S3/DynamoDB/Athena stores.

    Every stateful contract in the reference maps to an explicit local path
    (SURVEY.md section 2.4): the variants bucket's ``vcf-summaries/`` index
    prefix -> ``index_dir``; the metadata bucket's ORC tables + Athena
    database -> ``metadata_db`` (sqlite); the DynamoDB control tables
    (Datasets, VcfSummaries, VariantQueries, ...) -> ``ledger_db`` (sqlite);
    ontology tables (Ontologies/Anscestors/Descendants/OntoIndex) ->
    ``ontology_db``.
    """

    root: Path = Path("./beacon_data")

    @property
    def index_dir(self) -> Path:
        return self.root / "variant-index"

    @property
    def metadata_db(self) -> Path:
        return self.root / "metadata.sqlite"

    @property
    def ledger_db(self) -> Path:
        return self.root / "ledger.sqlite"

    @property
    def ontology_db(self) -> Path:
        return self.root / "ontology.sqlite"

    @property
    def query_results_dir(self) -> Path:
        """Async query result spill (reference: variant-queries/ S3 prefix)."""
        return self.root / "query-results"

    def ensure(self) -> "StorageConfig":
        for p in (self.root, self.index_dir, self.query_results_dir):
            p.mkdir(parents=True, exist_ok=True)
        return self


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Query/ingest engine tuning — the reference's in-code constants tier.

    window_cap: max candidate rows gathered per query around the searchsorted
      hit range (replaces the reference's 10kb-window x unbounded-scan shape,
      splitQuery SPLIT_SIZE=10000, with a fixed-shape gather the XLA compiler
      can tile).
    record_cap: max matched rows returned per query for record granularity
      (two-pass host fallback on overflow).
    ingest_shard_bytes: target uncompressed bytes per ingest slice
      (reference: summariseVcf cost-model; ABS_MAX_DATA_SPLIT 750MB,
      main.tf:16).
    max_index_rows_per_shard: device-side padding unit for index shards.
    """

    window_cap: int = 2048
    record_cap: int = 1024
    batch_size: int = 1024
    # mesh serving (SURVEY.md §2.5 fan-in mapping): when >1 device is
    # visible, multi-dataset queries run as ONE pjit program over the
    # dataset-sharded stack with psum fan-in (parallel/mesh.py) instead
    # of per-shard thread scatter; single-device falls back to scatter
    use_mesh: bool = True
    ingest_shard_bytes: int = 64 * 1024 * 1024
    ingest_workers: int = 8
    max_response_inline_bytes: int = 300 * 1024  # performQuery spill threshold
    request_timeout_s: float = 600.0  # variantutils REQUEST_TIMEOUT
    use_tpu: bool = True
    # serving micro-batcher (SURVEY.md §7): with wait=0 nobody waits
    # for company, and batches form from requests queuing behind a
    # leader that waits for a fetch-pipeline slot (continuous
    # batching); raise wait_ms to trade single-query latency for
    # fuller batches
    microbatch: bool = True
    microbatch_max: int = 512
    microbatch_wait_ms: float = 0.0
    # launched-but-unfetched kernel batches allowed per accumulator:
    # the launch/fetch overlap window (serving.py pipeline), taken by
    # the accumulator's leader BEFORE it pops. 1 = fully serial
    # launch->fetch (pre-fusion behavior); 2 double-buffers so host
    # encode of batch i+1 overlaps device execution of batch i
    fetch_pipeline_depth: int = 2
    # cross-shard fused dispatch: stack every warm device shard into
    # ONE device index (ops.kernel.FusedDeviceIndex) so a k-dataset
    # query costs one launch and concurrent queries against DIFFERENT
    # datasets coalesce into the same micro-batch. Costs a second
    # device-resident copy of the stacked columns (~48 B/row), so the
    # stack is skipped beyond fused_max_rows total rows (~3 GB at the
    # default).
    fused_dispatch: bool = True
    fused_max_rows: int = 64_000_000
    # response cache (response_cache.py): LRU in front of
    # engine.search keyed on (index fingerprint, normalized query,
    # response shaping); negative results cache too. size<=0 or
    # enabled=False disables; ttl_s=0 means no expiry.
    response_cache: bool = True
    response_cache_size: int = 4096
    response_cache_ttl_s: float = 300.0
    # chunk size for staged genotype-plane H2D uploads (plane_kernel):
    # planes larger than one chunk upload as pre-staged contiguous
    # chunks whose transfers overlap, instead of one giant synchronous
    # copy (the 28 MB/s config7 upload wall), each written on the device
    # into the resident padded plane. <=0 disables chunking: the whole
    # unpadded plane then stands beside the resident one until written.
    plane_upload_chunk_mb: int = 256
    # device-resident genotype planes (selected-samples leaf): upload a
    # shard's bit planes to HBM when their padded size fits the budget;
    # oversized plane sets stay host-resident (round-3 numpy path). The
    # budget leaves room for the column tiles + kernel workspace on a
    # 16 GB v5e.
    device_planes: bool = True
    plane_hbm_budget_gb: float = 11.0
    # region/dataset-scoped response-cache invalidation (ingest-while-
    # serving): a publish evicts only cached entries whose dataset set
    # AND coordinate bracket overlap the new rows, instead of dropping
    # the whole cache. Off restores the wholesale clear-on-publish.
    scoped_invalidation: bool = True
    # L0 delta-tail mini-index (ISSUE 15, the LSM memtable->L0 tier):
    # past EITHER threshold — tail depth in shards, or total tail rows
    # — a key's standing delta tail is stacked into a secondary fused
    # device index served by ONE batched launch, so deep tails stop
    # paying a per-shard host scan per query. 0 disables that trigger;
    # both 0 disables the L0 tier outright (every tail shard host-
    # scans, the pre-ISSUE-15 behaviour).
    l0_min_shards: int = 4
    l0_min_rows: int = 4096


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Slice-planning cost model (reference: summariseVcf constants
    :21-25 and the ABS_MAX_DATA_SPLIT / VCF_S3_OUTPUT_SIZE_LIMIT terraform
    ceilings, main.tf:16-17). The planner minimises total_time x cost over
    slice size — here 'dispatch' is a thread-pool task instead of an SNS
    message + lambda cold start, so the constants default far cheaper, but
    the optimiser itself is the same math."""

    min_task_time: float = 0.005  # MIN_SS_TIME (s)
    scan_rate: float = 200_000_000  # SS_RATE (compressed B/s, host parse)
    dispatch_cost: float = 0.0005  # SNS_TIME equivalent (s/task)
    max_concurrency: int = 64  # MAX_CONCURRENCY
    workers: int = 8  # parallel slice workers
    max_range_bytes: int = 750 * 1024 * 1024  # ABS_MAX_DATA_SPLIT
    # also materialise reference-layout binary region files per VCF
    # (vcf-summaries/ portable exchange format, index/portable.py)
    export_portable: bool = True
    # remote slice-scan workers (the reference's <=1000-lambda
    # summariseSlice fan-out): slice jobs scatter round-robin across
    # these worker URLs; empty = scan on this host's thread pool
    scan_worker_urls: tuple[str, ...] = ()
    scan_timeout_s: float = 120.0  # per-slice worker call budget
    scan_retries: int = 1  # extra workers tried before local fallback
    # ingest-while-serving (delta shards + background compaction):
    # stream_deltas publishes each completed slice of a FIRST-TIME
    # summarisation to the engine immediately as a queryable delta
    # shard (read-your-writes before the merge barrier); the base
    # publish is deferred to the compactor so the fused/mesh stacks and
    # the response cache are not demolished per submit. delta_max_shards
    # is the per-(dataset, vcf) delta-tail depth that kicks an early
    # compaction; compact_interval_s is the background compactor's
    # cadence (<=0 disables the thread — folds then only run on the
    # depth trigger or an explicit run_once()).
    stream_deltas: bool = True
    delta_max_shards: int = 8
    compact_interval_s: float = 30.0
    # size-tiered compaction (ISSUE 15): >0 arms the tiered fold
    # policy — raw delta tails fold into intermediate L1 artifacts
    # (persisted, epoch-ranged, adoptable after a crash) and the full
    # base merge only runs once the accumulated L1 bytes reach this
    # ratio of the base's bytes, so per-fold write amplification stops
    # scaling with base size. <=0 selects the legacy policy: every
    # fold is a full base merge. Tiered is the DEFAULT since ISSUE 20
    # (the config22 churn soak in BENCH_wirespeed: sustained multi-key
    # ingest folds L1 per trigger, base merges only at the ratio, GC
    # stays bounded); set BEACON_COMPACT_BASE_RATIO=0 to get the
    # legacy merge-every-fold behaviour back.
    compact_base_ratio: float = 0.35
    # superseded base/L1 artifacts are parked in a per-key .retired/
    # dir at each base merge and the newest N generations are kept;
    # older ones are GC'd (ingest.gc_bytes counts the reclaim). GC
    # only ever touches .retired/ — a serving artifact can never be
    # deleted.
    artifact_retain: int = 2
    # defer the end-of-summarisation BASE publish to the compactor
    # cadence as well (continuous-ingest mode): submits then never pay
    # a fingerprint bump / stack rebuild inline — the standing deltas
    # serve until the next fold. Off (default) keeps the base publish
    # at the end of each summarisation (identical post-submit state to
    # the pre-delta write path; slices still stream mid-scan).
    defer_base_publish: bool = False


# canonical external-service endpoints (reference indexer:40-42); the
# resolver clients in metadata/resolvers.py import these — single source
DEFAULT_OLS_URL = "https://www.ebi.ac.uk/ols/api/ontologies"
DEFAULT_ONTOSERVER_URL = (
    "https://r4.ontoserver.csiro.au/fhir/ValueSet/$expand"
)


@dataclasses.dataclass(frozen=True)
class ResolverConfig:
    """External ontology resolution (the indexer's OLS/Ontoserver calls,
    reference indexer/lambda_function.py:40-42). Off by default: an
    air-gapped deployment must not stall submissions on network timeouts;
    closures can also be loaded offline via OntologyStore."""

    enabled: bool = False
    ols_url: str = DEFAULT_OLS_URL
    ontoserver_url: str = DEFAULT_ONTOSERVER_URL
    workers: int = 8


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Failure envelope (resilience.py) — the knobs the reference got
    from the platform tier: API Gateway's 29 s hard timeout ->
    ``default_deadline_s``; Lambda reserved concurrency / API-GW
    throttling -> ``max_in_flight``; invoke retry + backoff ->
    the circuit breaker triple.

    default_deadline_s: request deadline when the client sends no
      ``X-Beacon-Deadline`` header; 0 disables. Ingest (``/submit``)
      is exempt from the *default* — a bulk VCF scan is a batch job,
      not a request — but an explicit header still applies there.
    batch_timeout_s: micro-batch submit bound — even deadline-less
      callers cannot block on a wedged kernel launch forever.
    max_in_flight: admission cap; excess requests answer 429 +
      Retry-After instead of queueing.
    runner_workers / runner_max_pending: the async query runner's
      bounded pool (replaces thread-per-query) and its shed threshold.
    breaker_*: consecutive-failure circuit breaker on per-worker routes.
    failover_retries: extra replicas a failed worker-search leg may
      re-route to (never the same copy twice) before its datasets fall
      to the partial-results path.
    partial_results: when no replica of a dataset is reachable, answer
      with the datasets that responded and mark the rest in the
      envelope (``meta.unavailableDatasets`` + a warning) instead of
      failing the whole request; off restores fail-the-query semantics.
    """

    default_deadline_s: float = 60.0
    batch_timeout_s: float = 60.0
    max_in_flight: int = 256
    shed_retry_after_s: float = 1.0
    runner_workers: int = 8
    runner_max_pending: int = 64
    # share of runner_max_pending the bulk lane may hold (lane-aware
    # admission, shaping.py lanes): record-retrieval floods saturate at
    # this fraction while interactive submissions keep the rest
    runner_bulk_share: float = 0.5
    breaker_failure_threshold: int = 5
    breaker_reset_s: float = 30.0
    breaker_half_open_probes: int = 1
    failover_retries: int = 2
    partial_results: bool = True


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Coordinator->worker data-plane knobs (parallel/transport.py).

    The reference's fan-out rode SNS + Lambda invokes, paying per-call
    setup at the platform tier; here the same costs are explicit TCP
    handshakes and JSON bytes, and each has a knob:

    pool_size: keep-alive connections kept per worker host. Not a
      concurrency cap — a scatter burst beyond it opens extra
      connections that are closed, not pooled, on return.
    idle_ttl_s: pooled connections idle longer than this are closed on
      next touch (workers reap their side slightly later).
    gzip_min_bytes: request bodies at or over this size are
      gzip-compressed on the wire (0 disables).
    hedge_delay_s: request hedging (Dean & Barroso, The Tail at
      Scale): if a call's primary worker has not answered within this
      delay, the same call is raced on a second worker and the first
      response wins. >0 = fixed delay; 0 = adaptive (the p95 of recent
      RTTs, once enough samples exist); <0 disables. Governs both
      ingest slice scans and (with ``replica_hedge``) full /search
      calls across replicas.
    bool_short_circuit: boolean-granularity fan-outs return as soon as
      any worker reports a hit, abandoning the rest of the scatter.
    replica_hedge: hedge slow /search primaries with a second replica
      of the same datasets (``hedge_delay_s`` semantics unchanged);
      single-replica fleets never hedge.
    """

    pool_size: int = 4
    idle_ttl_s: float = 60.0
    gzip_min_bytes: int = 32 * 1024
    hedge_delay_s: float = 0.0
    bool_short_circuit: bool = True
    replica_hedge: bool = True


@dataclasses.dataclass(frozen=True)
class ShapingConfig:
    """Traffic shaping & brownout (shaping.py) — the explicit version
    of the reference's platform tier (API Gateway usage-plan throttling
    + Lambda reserved concurrency): weighted fair queueing across
    tenants, priority lanes, adaptive Retry-After, and an SLO-driven
    brownout ladder.

    enabled: the whole layer on/off (off restores the PR-1 global-gate
      behaviour).
    tenant_header: header carrying an explicit tenant id; requests
      without it bucket by Authorization hash, else ``anon``.
    tenant_weights: ``tenant=weight`` comma list for the DRR drain
      ratio (``gold=4,free=1``); unlisted tenants get
      ``default_weight``.
    tenant_max_in_flight / tenant_queue_depth: per-tenant running cap
      and per-tenant per-lane queue bound; a full queue sheds 429 with
      the adaptive Retry-After.
    max_queue_wait_s: a queued request not granted within this bound
      sheds (its request deadline may cut earlier -> 504).
    bulk_starvation_ms: a bulk waiter older than this is served ahead
      of the interactive lane (one per dispatch pass) — the escape
      hatch that keeps strict lane precedence from starving bulk.
    retry_after_floor_s / retry_after_ceil_s: clamp on the adaptive
      Retry-After (p90 of the shed lane's measured queue wait).
    max_tenants: distinct tenant states (and metric label values)
      tracked before new ids share the ``overflow`` bucket.
    brownout*: the ladder — sustained SLO breach steps up
      (hedge off -> bulk pause -> AIMD cap squeeze -> global shed)
      after ``up_hold_s``; sustained recovery steps down after
      ``down_hold_s`` (hysteresis), restoring squeezed caps by
      ``ai_step`` per tick (additive increase over ``md_factor``
      multiplicative decrease).
    """

    enabled: bool = True
    tenant_header: str = "X-Beacon-Tenant"
    tenant_weights: str = ""
    default_weight: float = 1.0
    tenant_max_in_flight: int = 64
    tenant_queue_depth: int = 128
    max_queue_wait_s: float = 10.0
    bulk_starvation_ms: float = 500.0
    retry_after_floor_s: float = 1.0
    retry_after_ceil_s: float = 60.0
    max_tenants: int = 64
    brownout: bool = True
    brownout_up_hold_s: float = 3.0
    brownout_down_hold_s: float = 15.0
    brownout_md_factor: float = 0.5
    brownout_ai_step: float = 0.25
    brownout_min_scale: float = 0.125
    # cost-aware DRR (accounting.py scheduling seam): the fair queue
    # charges a grant the MEASURED mean cost of its query shape
    # (normalized to the lane mean, clamped [0.25, 2.0]) instead of
    # the flat 1-per-request deficit. Off (default) keeps the flat
    # charge byte-identical — observability first, scheduling to be
    # proven on a benchmark cell before it defaults on.
    cost_drr: bool = False


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """Telemetry-plane knobs (telemetry.py). Tracing itself stays
    env-gated (``SBEACON_TRACE=1``, utils/trace.py) like the
    reference's ``#define INCLUDE_STOP_WATCH``; these knobs cover the
    always-on surfaces built on top of it.

    slow_query_ms: any request slower than this emits one structured
      JSON line (trace id, route, stage notes) to the
      ``sbeacon.slowquery`` logger and the in-memory ring served at
      ``/_trace``. 0 records every request (debug); negative disables.
    slow_query_log: optional file the slow-query JSON lines append to.
    profiler_port: port of ``jax.profiler.start_server`` (0 = off,
      ``BEACON_PROFILER_PORT``): an operator asks a running server for a
      bounded capture, in which the ``beacon.<stage>`` annotations of
      utils/trace.py sit beside the device lines.

    SLO engine (slo.py, served at ``/slo`` + ``slo.*`` gauges):
    slo_availability_target: default max-good-ratio objective per route
      (0.999 = at most 0.1% 5xx within budget).
    slo_latency_ms / slo_latency_target: default latency objective —
      at least ``slo_latency_target`` of non-5xx requests under
      ``slo_latency_ms`` milliseconds.
    slo_routes: per-route overrides, compact
      ``route:field=value[:field=value...]`` comma list (e.g.
      ``g_variants:latency_ms=50,info:availability=0.99``).
    slo_alert_burn_rate: burn factor that, sustained on BOTH the fast
      (5m) and slow (1h) windows, marks a route breached (14.4 is the
      SRE-workbook fast-page factor).

    Flight recorder (telemetry.EventJournal, served at ``/ops/events``):
    event_journal: enables control-plane event publication.
    event_journal_size: events kept in the bounded ring.

    Cost accounting (accounting.py, served at ``/ops/costs``):
    cost_accounting: fold every tracked request's CostVector into the
      per-(tenant, lane, query-shape) table + the ``cost.*`` series.
    cost_window_s: the decaying window the per-shape mean cost (and
      the DRR charge hook) is computed over.
    Tenant cardinality reuses shaping's ``max_tenants`` cap.

    Fleet observability & canaries (ISSUE 12):
    fleet_digest_interval_s: minimum seconds between worker
      ``/ops/digest`` collection passes behind ``/fleet/status``
      (digests are polled lazily, at most once per interval).
    canary_enabled / canary_interval_s: the known-answer canary prober
      (canary.py) — background expected-answer probes per dataset x
      query shape x dispatch path; interval <= 0 disables the thread
      (explicit ``run_once()`` still works).
    canary_latency_ms: a correct probe slower than this ticks
      ``canary.slow_probes``.

    Device-plane flight recorder (telemetry.DeviceFlightRecorder,
    served at ``/device/status``; ISSUE 14):
    device_ring_size: per-launch records kept in the bounded launch
      ring (``BEACON_DEVICE_RING_SIZE``).
    compile_tracking: track first-seen (program, shape) compile keys;
      a compile outside warmup emits a ``device.compile`` journal
      event and ticks ``device.mid_request_compiles``
      (``BEACON_COMPILE_TRACKING``).

    Live shard migration (parallel/migration.py; ISSUE 16):
    migration_enabled: serve ``POST /fleet/migrate``
      (``BEACON_MIGRATION_ENABLED``; ``GET /fleet/migrations`` always
      answers — observing history is never disabled).
    migration_verify_rounds: consecutive CLEAN canary-verify rounds
      the target must answer before cut-over
      (``BEACON_MIGRATION_VERIFY_ROUNDS``, floor 1).
    migration_copy_timeout_s: wall budget for the copy phase; also
      the base of the stuck-migration diagnosis
      (``BEACON_MIGRATION_COPY_TIMEOUT_S``).

    Execution-plan plane (plan.py, served at ``GET /ops/plans``;
    ISSUE 19):
    explain_enabled: serve ``?explain=1`` inline execution plans under
      ``meta.executionPlan`` (``BEACON_EXPLAIN_ENABLED``; worker-token
      protected when one is set — 404 when disabled, 401/403 on a
      missing/bad token). The sampled plan store and drift sentinel
      run regardless; this gates only the inline surface.
    plan_sample_n: retain the full stage document for every Nth
      observation per (query-shape, plan-shape) aggregate
      (``BEACON_PLAN_SAMPLE_N``; counting is always exact — sampling
      bounds only the retained exemplar documents).
    plan_drift_windows: closed observation windows retained per
      query-shape for the dominant-plan-shape comparison
      (``BEACON_PLAN_DRIFT_WINDOWS``, floor 2: newest vs previous).
    """

    slow_query_ms: float = 1000.0
    slow_query_log: str = ""
    profiler_port: int = 0
    slo_availability_target: float = 0.999
    slo_latency_ms: float = 250.0
    slo_latency_target: float = 0.99
    slo_routes: str = ""
    slo_alert_burn_rate: float = 14.4
    event_journal: bool = True
    event_journal_size: int = 1024
    cost_accounting: bool = True
    cost_window_s: float = 300.0
    fleet_digest_interval_s: float = 10.0
    canary_enabled: bool = True
    canary_interval_s: float = 30.0
    canary_latency_ms: float = 1000.0
    device_ring_size: int = 256
    compile_tracking: bool = True
    migration_enabled: bool = True
    migration_verify_rounds: int = 3
    migration_copy_timeout_s: float = 120.0
    explain_enabled: bool = False
    plan_sample_n: int = 16
    plan_drift_windows: int = 2


@dataclasses.dataclass(frozen=True)
class AuthConfig:
    """Authentication for the two trust boundaries the reference gates
    with IAM: the mutating ``/submit`` route (reference: api.tf:120-149,
    AWS_IAM authorizer) and the worker-invoke boundary (reference: direct
    Lambda invoke / SNS, IAM-authenticated).

    Empty token = open (dev mode, matches round-1 behavior). Set
    ``submit_token`` to require ``Authorization: Bearer <token>`` on
    POST/PATCH ``/submit``; set ``worker_token`` to require the same on
    every coordinator->worker HTTP call (except ``/health``). Workers
    should additionally only be reachable on a private network — the
    token is defense-in-depth, not a substitute for network isolation.
    """

    submit_token: str = ""
    worker_token: str = ""


@dataclasses.dataclass(frozen=True)
class BeaconConfig:
    info: BeaconInfo = dataclasses.field(default_factory=BeaconInfo)
    storage: StorageConfig = dataclasses.field(default_factory=StorageConfig)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    ingest: IngestConfig = dataclasses.field(default_factory=IngestConfig)
    resolvers: ResolverConfig = dataclasses.field(
        default_factory=ResolverConfig
    )
    auth: AuthConfig = dataclasses.field(default_factory=AuthConfig)
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig
    )
    observability: ObservabilityConfig = dataclasses.field(
        default_factory=ObservabilityConfig
    )
    transport: TransportConfig = dataclasses.field(
        default_factory=TransportConfig
    )
    shaping: ShapingConfig = dataclasses.field(
        default_factory=ShapingConfig
    )

    @staticmethod
    def from_env(root: str | os.PathLike | None = None) -> "BeaconConfig":
        """Build config with env-var overrides (reference env-var tier)."""
        env = os.environ
        info = BeaconInfo(
            beacon_id=env.get("BEACON_ID", BeaconInfo.beacon_id),
            beacon_name=env.get("BEACON_NAME", BeaconInfo.beacon_name),
            api_version=env.get("BEACON_API_VERSION", BeaconInfo.api_version),
            environment=env.get("BEACON_ENVIRONMENT", BeaconInfo.environment),
            uri=env.get("BEACON_URL", BeaconInfo.uri),
        )
        storage = StorageConfig(
            root=Path(root or env.get("BEACON_DATA_ROOT", "./beacon_data"))
        )
        eng_over = {}
        if "BEACON_WINDOW_CAP" in env:
            eng_over["window_cap"] = int(env["BEACON_WINDOW_CAP"])
        if "BEACON_RECORD_CAP" in env:
            eng_over["record_cap"] = int(env["BEACON_RECORD_CAP"])
        _off = ENV_OFF
        if "BEACON_USE_TPU" in env:
            eng_over["use_tpu"] = env["BEACON_USE_TPU"].lower() not in _off
        if "BEACON_USE_MESH" in env:
            eng_over["use_mesh"] = (
                env["BEACON_USE_MESH"].lower() not in _off
            )
        if "BEACON_PLANE_HBM_BUDGET_GB" in env:
            eng_over["plane_hbm_budget_gb"] = float(
                env["BEACON_PLANE_HBM_BUDGET_GB"]
            )
        if "BEACON_FUSED_DISPATCH" in env:
            eng_over["fused_dispatch"] = (
                env["BEACON_FUSED_DISPATCH"].lower() not in _off
            )
        if "BEACON_FUSED_MAX_ROWS" in env:
            eng_over["fused_max_rows"] = int(env["BEACON_FUSED_MAX_ROWS"])
        if "BEACON_RESPONSE_CACHE" in env:
            eng_over["response_cache"] = (
                env["BEACON_RESPONSE_CACHE"].lower() not in _off
            )
        if "BEACON_RESPONSE_CACHE_SIZE" in env:
            eng_over["response_cache_size"] = int(
                env["BEACON_RESPONSE_CACHE_SIZE"]
            )
        if "BEACON_RESPONSE_CACHE_TTL_S" in env:
            eng_over["response_cache_ttl_s"] = float(
                env["BEACON_RESPONSE_CACHE_TTL_S"]
            )
        if "BEACON_SCOPED_INVALIDATION" in env:
            eng_over["scoped_invalidation"] = (
                env["BEACON_SCOPED_INVALIDATION"].lower() not in _off
            )
        if "BEACON_L0_MIN_SHARDS" in env:
            eng_over["l0_min_shards"] = int(env["BEACON_L0_MIN_SHARDS"])
        if "BEACON_L0_MIN_ROWS" in env:
            eng_over["l0_min_rows"] = int(env["BEACON_L0_MIN_ROWS"])
        if "BEACON_FETCH_PIPELINE_DEPTH" in env:
            eng_over["fetch_pipeline_depth"] = int(
                env["BEACON_FETCH_PIPELINE_DEPTH"]
            )
        if "BEACON_PLANE_UPLOAD_CHUNK_MB" in env:
            eng_over["plane_upload_chunk_mb"] = int(
                env["BEACON_PLANE_UPLOAD_CHUNK_MB"]
            )
        engine = EngineConfig(**eng_over)
        resolvers = ResolverConfig(
            enabled=env.get("BEACON_RESOLVE_ONTOLOGIES", "").lower()
            in ("1", "true", "yes", "on"),
            ols_url=env.get("BEACON_OLS_URL", DEFAULT_OLS_URL),
            ontoserver_url=env.get(
                "BEACON_ONTOSERVER_URL", DEFAULT_ONTOSERVER_URL
            ),
            workers=int(env.get("BEACON_RESOLVER_WORKERS", "8")),
        )
        ingest_over = {}
        if "BEACON_SCAN_WORKERS" in env:
            ingest_over["scan_worker_urls"] = tuple(
                u.strip()
                for u in env["BEACON_SCAN_WORKERS"].split(",")
                if u.strip()
            )
        if "BEACON_INGEST_WORKERS" in env:
            ingest_over["workers"] = int(env["BEACON_INGEST_WORKERS"])
        if "BEACON_STREAM_DELTAS" in env:
            ingest_over["stream_deltas"] = (
                env["BEACON_STREAM_DELTAS"].lower() not in _off
            )
        if "BEACON_DELTA_MAX_SHARDS" in env:
            ingest_over["delta_max_shards"] = int(
                env["BEACON_DELTA_MAX_SHARDS"]
            )
        if "BEACON_COMPACT_INTERVAL_S" in env:
            ingest_over["compact_interval_s"] = float(
                env["BEACON_COMPACT_INTERVAL_S"]
            )
        if "BEACON_COMPACT_BASE_RATIO" in env:
            ingest_over["compact_base_ratio"] = float(
                env["BEACON_COMPACT_BASE_RATIO"]
            )
        if "BEACON_ARTIFACT_RETAIN" in env:
            ingest_over["artifact_retain"] = int(
                env["BEACON_ARTIFACT_RETAIN"]
            )
        if "BEACON_DEFER_BASE_PUBLISH" in env:
            ingest_over["defer_base_publish"] = (
                env["BEACON_DEFER_BASE_PUBLISH"].lower() not in _off
            )
        ingest = IngestConfig(**ingest_over)
        auth = AuthConfig(
            submit_token=env.get("BEACON_SUBMIT_TOKEN", ""),
            worker_token=env.get("BEACON_WORKER_TOKEN", ""),
        )
        res_over: dict = {}
        _res_env = {
            "BEACON_DEADLINE_S": ("default_deadline_s", float),
            "BEACON_BATCH_TIMEOUT_S": ("batch_timeout_s", float),
            "BEACON_MAX_IN_FLIGHT": ("max_in_flight", int),
            "BEACON_SHED_RETRY_AFTER_S": ("shed_retry_after_s", float),
            "BEACON_RUNNER_WORKERS": ("runner_workers", int),
            "BEACON_RUNNER_MAX_PENDING": ("runner_max_pending", int),
            "BEACON_BREAKER_THRESHOLD": ("breaker_failure_threshold", int),
            "BEACON_BREAKER_RESET_S": ("breaker_reset_s", float),
            "BEACON_BREAKER_PROBES": ("breaker_half_open_probes", int),
            "BEACON_FAILOVER_RETRIES": ("failover_retries", int),
            "BEACON_RUNNER_BULK_SHARE": ("runner_bulk_share", float),
        }
        for var, (field, conv) in _res_env.items():
            if var in env:
                res_over[field] = conv(env[var])
        if "BEACON_PARTIAL_RESULTS" in env:
            res_over["partial_results"] = (
                env["BEACON_PARTIAL_RESULTS"].lower() not in _off
            )
        resilience = ResilienceConfig(**res_over)
        tr_over: dict = {}
        _tr_env = {
            "BEACON_POOL_SIZE": ("pool_size", int),
            "BEACON_POOL_IDLE_S": ("idle_ttl_s", float),
            "BEACON_GZIP_MIN_BYTES": ("gzip_min_bytes", int),
            "BEACON_HEDGE_DELAY_S": ("hedge_delay_s", float),
        }
        for var, (field, conv) in _tr_env.items():
            if var in env:
                tr_over[field] = conv(env[var])
        if "BEACON_BOOL_SHORT_CIRCUIT" in env:
            tr_over["bool_short_circuit"] = (
                env["BEACON_BOOL_SHORT_CIRCUIT"].lower() not in _off
            )
        if "BEACON_REPLICA_HEDGE" in env:
            tr_over["replica_hedge"] = (
                env["BEACON_REPLICA_HEDGE"].lower() not in _off
            )
        transport = TransportConfig(**tr_over)
        obs_over: dict = {}
        if "SBEACON_SLOW_QUERY_MS" in env:
            obs_over["slow_query_ms"] = float(env["SBEACON_SLOW_QUERY_MS"])
        if "SBEACON_SLOW_QUERY_LOG" in env:
            obs_over["slow_query_log"] = env["SBEACON_SLOW_QUERY_LOG"]
        _obs_env = {
            "BEACON_PROFILER_PORT": ("profiler_port", int),
            "BEACON_SLO_AVAILABILITY": ("slo_availability_target", float),
            "BEACON_SLO_LATENCY_MS": ("slo_latency_ms", float),
            "BEACON_SLO_LATENCY_TARGET": ("slo_latency_target", float),
            "BEACON_SLO_ROUTES": ("slo_routes", str),
            "BEACON_SLO_ALERT_BURN": ("slo_alert_burn_rate", float),
            "BEACON_EVENT_JOURNAL_SIZE": ("event_journal_size", int),
            "BEACON_FLEET_DIGEST_INTERVAL_S": (
                "fleet_digest_interval_s",
                float,
            ),
            "BEACON_CANARY_INTERVAL_S": ("canary_interval_s", float),
            "BEACON_CANARY_LATENCY_MS": ("canary_latency_ms", float),
            "BEACON_DEVICE_RING_SIZE": ("device_ring_size", int),
            "BEACON_MIGRATION_VERIFY_ROUNDS": (
                "migration_verify_rounds",
                int,
            ),
            "BEACON_MIGRATION_COPY_TIMEOUT_S": (
                "migration_copy_timeout_s",
                float,
            ),
            "BEACON_PLAN_SAMPLE_N": ("plan_sample_n", int),
            "BEACON_PLAN_DRIFT_WINDOWS": ("plan_drift_windows", int),
        }
        for var, (field, conv) in _obs_env.items():
            if var in env:
                obs_over[field] = conv(env[var])
        if "BEACON_EVENT_JOURNAL_ENABLED" in env:
            obs_over["event_journal"] = (
                env["BEACON_EVENT_JOURNAL_ENABLED"].lower() not in _off
            )
        if "BEACON_CANARY_ENABLED" in env:
            obs_over["canary_enabled"] = (
                env["BEACON_CANARY_ENABLED"].lower() not in _off
            )
        if "BEACON_COMPILE_TRACKING" in env:
            obs_over["compile_tracking"] = (
                env["BEACON_COMPILE_TRACKING"].lower() not in _off
            )
        if "BEACON_MIGRATION_ENABLED" in env:
            obs_over["migration_enabled"] = (
                env["BEACON_MIGRATION_ENABLED"].lower() not in _off
            )
        if "BEACON_EXPLAIN_ENABLED" in env:
            obs_over["explain_enabled"] = (
                env["BEACON_EXPLAIN_ENABLED"].lower() not in _off
            )
        if "BEACON_COST_ACCOUNTING" in env:
            obs_over["cost_accounting"] = (
                env["BEACON_COST_ACCOUNTING"].lower() not in _off
            )
        if "BEACON_COST_WINDOW_S" in env:
            obs_over["cost_window_s"] = float(env["BEACON_COST_WINDOW_S"])
        observability = ObservabilityConfig(**obs_over)
        sh_over: dict = {}
        _sh_env = {
            "BEACON_TENANT_HEADER": ("tenant_header", str),
            "BEACON_TENANT_WEIGHTS": ("tenant_weights", str),
            "BEACON_TENANT_DEFAULT_WEIGHT": ("default_weight", float),
            "BEACON_TENANT_MAX_IN_FLIGHT": ("tenant_max_in_flight", int),
            "BEACON_TENANT_QUEUE_DEPTH": ("tenant_queue_depth", int),
            "BEACON_MAX_QUEUE_WAIT_S": ("max_queue_wait_s", float),
            "BEACON_BULK_STARVATION_MS": ("bulk_starvation_ms", float),
            "BEACON_RETRY_AFTER_FLOOR_S": ("retry_after_floor_s", float),
            "BEACON_RETRY_AFTER_CEIL_S": ("retry_after_ceil_s", float),
            "BEACON_MAX_TENANTS": ("max_tenants", int),
            "BEACON_BROWNOUT_UP_S": ("brownout_up_hold_s", float),
            "BEACON_BROWNOUT_DOWN_S": ("brownout_down_hold_s", float),
        }
        for var, (field, conv) in _sh_env.items():
            if var in env:
                sh_over[field] = conv(env[var])
        if "BEACON_SHAPING" in env:
            sh_over["enabled"] = env["BEACON_SHAPING"].lower() not in _off
        if "BEACON_BROWNOUT" in env:
            sh_over["brownout"] = env["BEACON_BROWNOUT"].lower() not in _off
        if "BEACON_COST_DRR" in env:
            sh_over["cost_drr"] = env["BEACON_COST_DRR"].lower() not in _off
        shaping = ShapingConfig(**sh_over)
        return BeaconConfig(
            info=info,
            storage=storage,
            engine=engine,
            ingest=ingest,
            resolvers=resolvers,
            auth=auth,
            resilience=resilience,
            observability=observability,
            transport=transport,
            shaping=shaping,
        )

    def dumps(self) -> str:
        d = dataclasses.asdict(self)
        d["storage"]["root"] = str(d["storage"]["root"])
        return json.dumps(d, indent=2)


#: where compiled device programs persist when the environment does
#: not say: ONE fixed directory in the checkout. A cache that follows a
#: data root, a temporary directory, a pid or the clock is never found
#: again by the next process.
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_persistent_compile_cache() -> Path:
    """Turn on XLA's persistent compilation cache and return its
    directory, so the warmed kernel programs compile once per index and
    config shape, not once per process start. Every deployment entry
    calls this (api.server, parallel.dispatch, chip_smoke.py).

    ``JAX_COMPILATION_CACHE_DIR`` decides the PLACE: JAX reads the
    variable itself, so when it is set no directory is set here. Unset,
    the cache lives at :data:`COMPILE_CACHE_DIR`. Raises ``OSError``
    when that directory cannot be created: a server may log that and
    start cold, the chip smoke treats it as a failure.

    Wherever it lives the cache keeps EVERY program, not only those
    that took a second to compile (JAX's default threshold): a warmup
    is dozens of sub-second programs (half a warm restart on the v5e
    went to recompiling them), and a threshold on compile time makes
    what a run adds to the cache depend on the clock. An operator's own
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` still wins."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return Path(env_dir)
    COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return COMPILE_CACHE_DIR
