"""Unified telemetry plane: metrics registry, request tracing, profiling.

The reference's only observability is a compile-gated C++ stopwatch
(reference: lambda/summariseSlice/source/stopwatch.h) and
print-to-CloudWatch logging; its request-identity story is the
``VariantQuery.startTime/endTime/elapsedTime`` DynamoDB columns
(shared_resources/dynamodb/variant_queries.py:29-59) — timing without a
propagated identity. After PR 1-2 this repo's own telemetry had
fragmented the same way: ``/metrics`` hand-assembled nested dicts from
the batcher, admission controller, breakers and response cache, and the
``Tracer`` in ``utils/trace.py`` was process-local with no request id
crossing the coordinator->worker HTTP boundary.

This module is the single plane the stack wires through:

- **Metrics registry** (:class:`MetricsRegistry`): typed
  :class:`Counter` / :class:`Gauge` / :class:`Histogram` instruments
  with stable dotted names and optional one-label fan-out. Producers
  register instruments (value-owning or callback-backed, the Prometheus
  collector style — the callback reads state the producer already
  maintains under its own lock); the registry renders one snapshot as
  nested JSON (back-compat with the old hand-assembled ``/metrics``
  shape) or as Prometheus text exposition.
- **Request context** (:class:`RequestContext`): a trace id minted at
  API ingress (or honored from an inbound ``X-Beacon-Trace`` header),
  carried thread-locally and re-installed across the pool hand-offs the
  batcher and async runner already do for deadlines, propagated as a
  header on every coordinator->worker call so worker-side spans parent
  correctly (the Dapper model), and returned in the response envelope.
- **Flight recorder** (:class:`EventJournal`): a bounded structured
  journal the control plane publishes transition events into (breaker
  state changes, replica failovers, hedges, rediscovery passes,
  route-table publishes, cache invalidations — wholesale and scoped,
  delta-shard publishes ``ingest.delta_publish``, compaction
  ``compaction.start``/``compaction.complete``, admission sheds), each
  stamped with monotonic + wall time and the ambient trace id; served
  at ``/ops/events``. Histograms can additionally carry **exemplars**
  — the trace id of the latest observation per bucket — so a slow
  latency bucket links directly to the request that landed in it.
- **Slow-query hook**: :class:`SlowQueryLog` records a structured JSON
  line (trace id, route, stage decomposition, outcome notes) for every
  request above a configurable latency threshold. (The program's
  regions in a device profile are the ``beacon.<stage>`` annotations
  of ``utils/trace.py``.)

Everything here is stdlib-only and importable from any layer, like
resilience.py.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import re
import threading
import time
import uuid
from contextlib import contextmanager

log = logging.getLogger(__name__)

# -- metric instruments -------------------------------------------------------

#: fixed request/stage latency bucket upper bounds, in milliseconds
#: (Prometheus-style cumulative buckets; +Inf is implicit)
LATENCY_BUCKETS_MS = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
)

#: instrument names are stable dotted lowercase identifiers —
#: ``tools/check_metric_names.py`` enforces the same grammar statically
_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")


#: default cap on distinct label values a value-owning instrument may
#: mint per family; overflow collapses to :data:`OVERFLOW_LABEL` and
#: ticks the registry's ``telemetry.label_overflow`` counter — the
#: registry-level twin of shaping's 64-tenant cap, so NO producer can
#: turn attacker-controlled input into unbounded series
DEFAULT_MAX_LABEL_VALUES = 64
#: the shared bucket overflowing label values collapse into
OVERFLOW_LABEL = "other"


class _Instrument:
    """Shared base: a named, optionally labeled, typed series.

    ``fn`` makes the instrument callback-backed (collector style): the
    callback returns the current value — a number, or a
    ``{label_value: number}`` dict when ``label`` is set. Without
    ``fn`` the instrument owns its value(s) under a short lock.

    ``label`` may also be a TUPLE of label names (e.g. ``("route",
    "window")``): the value dict is then keyed by matching tuples of
    label values, rendered as multi-label Prometheus series and as
    nested maps in the JSON snapshot.

    Value-owning labeled instruments enforce a **cardinality guard**:
    at most ``max_label_values`` distinct label values are ever minted
    per family; further values collapse into the shared ``"other"``
    bucket and tick ``telemetry.label_overflow{family=...}``. (Before
    this guard only shaping's tenant classifier enforced a cap — the
    registry itself would happily mint a series per attacker-chosen
    header value.) Callback-backed instruments are exempt: their
    producer owns the state and its bounds.
    """

    kind = "untyped"

    def __init__(self, name: str, help: str = "", *,
                 fn=None, label=None, json_render: bool = True,
                 max_label_values: int | None = None):
        if not _NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} must be dotted lowercase "
                "(e.g. 'batcher.launches')"
            )
        self.name = name
        self.help = help
        self.fn = fn
        self.label = label
        #: normalized label-name tuple (None = unlabeled)
        self.labels: tuple[str, ...] | None = (
            None
            if label is None
            else (label,) if isinstance(label, str) else tuple(label)
        )
        #: False = Prometheus-only (used where the back-compat JSON
        #: shape differs from the dotted nesting, e.g. breaker state)
        self.json_render = json_render
        self.max_label_values = int(
            max_label_values
            if max_label_values is not None
            else DEFAULT_MAX_LABEL_VALUES
        )
        #: the registry's shared label-overflow counter (set at
        #: registration; None on free-standing instruments)
        self._overflow = None
        self._lock = threading.Lock()
        self._value = 0.0
        self._children: dict[str, float] = {}

    def _guard_label(self, label_value, children: dict):
        """The label value to actually mint, under the cardinality
        guard (call holding ``self._lock``): a NEW value on a family
        already at its cap collapses to ``"other"``."""
        if (
            label_value is None
            or label_value in children
            or len(children) < self.max_label_values
        ):
            return label_value
        ov = self._overflow
        if ov is not None and ov is not self:
            ov.inc(label_value=self.name)
        if isinstance(label_value, tuple):
            return (OVERFLOW_LABEL,) * len(label_value)
        return OVERFLOW_LABEL

    def _bump(self, n: float, label_value: str | None) -> None:
        with self._lock:
            if label_value is None:
                self._value += n
            else:
                label_value = self._guard_label(
                    label_value, self._children
                )
                self._children[label_value] = (
                    self._children.get(label_value, 0.0) + n
                )

    def collect(self):
        """Current value: a number, or {label_value: number}."""
        if self.fn is not None:
            try:
                return self.fn()
            except Exception:  # a broken callback must not kill /metrics
                log.exception("metric %s callback failed", self.name)
                return None
        with self._lock:
            if self.label is not None:
                return dict(self._children)
            return self._value


class Counter(_Instrument):
    """Monotonic cumulative count (requests served, cache hits)."""

    kind = "counter"

    def inc(self, n: float = 1.0, *, label_value: str | None = None) -> None:
        self._bump(n, label_value)


class Gauge(_Instrument):
    """Point-in-time level (queue depth, entries resident)."""

    kind = "gauge"

    def set(self, v: float, *, label_value: str | None = None) -> None:
        with self._lock:
            if label_value is None:
                self._value = float(v)
            else:
                label_value = self._guard_label(
                    label_value, self._children
                )
                self._children[label_value] = float(v)


class Histogram(_Instrument):
    """Fixed-bucket latency histogram with per-label-value children.

    ``observe`` is the hot-path entry: one short lock, one linear
    bucket scan over the fixed boundary tuple (13 compares) — no
    allocation. Buckets are cumulative at render time, Prometheus
    semantics.

    With ``exemplars=True`` each observation may carry a trace id
    (explicit ``exemplar=`` argument, or the ambient request context's
    id): the most recent (trace id, value, wall time) is kept per
    bucket, so a slow bucket on a dashboard links straight to the
    distributed trace that landed in it (``/_trace?trace_id=...``).
    Rendered as OpenMetrics ``# {trace_id="..."} value ts`` suffixes in
    the text exposition and an ``exemplars`` map in the JSON snapshot.
    Memory is bounded by (label values x buckets) — one slot each.
    """

    kind = "histogram"

    def __init__(self, name: str, help: str = "", *,
                 buckets: tuple = LATENCY_BUCKETS_MS,
                 label: str | None = None,
                 exemplars: bool = False,
                 max_label_values: int | None = None):
        super().__init__(name, help, label=label,
                         max_label_values=max_label_values)
        self.buckets = tuple(float(b) for b in buckets)
        self.exemplars_enabled = bool(exemplars)
        # label_value (or "") -> [counts per bucket + overflow, count, sum]
        self._series: dict[str, list] = {}
        # (label_value, le) -> (trace_id, observed value, wall time)
        self._exemplars: dict[tuple[str, str], tuple] = {}

    def observe(self, v: float, *, label_value: str | None = None,
                exemplar: str | None = None) -> None:
        key = label_value if label_value is not None else ""
        if self.exemplars_enabled and exemplar is None:
            ctx = current_context()
            if ctx is not None:
                exemplar = ctx.trace_id
        with self._lock:
            if key:
                key = self._guard_label(key, self._series)
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = [
                    [0] * (len(self.buckets) + 1), 0, 0.0
                ]
            counts, _n, _sum = s
            le = "+Inf"
            for i, b in enumerate(self.buckets):
                if v <= b:
                    counts[i] += 1
                    le = f"{b:g}"
                    break
            else:
                counts[-1] += 1
            s[1] += 1
            s[2] += v
            if self.exemplars_enabled and exemplar:
                self._exemplars[(key, le)] = (exemplar, v, time.time())

    def collect(self):
        """{label_value: {"count", "sum", "buckets": {le: cumulative}
        [, "exemplars": {le: {traceId, value, time}}]}} (unlabeled
        histograms use the single key ``""``)."""
        out = {}
        with self._lock:
            for key, (counts, n, total) in self._series.items():
                cum, acc = {}, 0
                for b, c in zip(self.buckets, counts):
                    acc += c
                    cum[f"{b:g}"] = acc
                cum["+Inf"] = acc + counts[-1]
                out[key] = {
                    "count": n,
                    "sum": round(total, 3),
                    "buckets": cum,
                }
            for (key, le), (tid, v, t) in self._exemplars.items():
                series = out.get(key)
                if series is not None:
                    series.setdefault("exemplars", {})[le] = {
                        "traceId": tid,
                        "value": round(v, 4),
                        "time": round(t, 3),
                    }
        return out


class MetricsRegistry:
    """One process surface of typed series with stable dotted names.

    Registration raises on duplicates so renames/collisions break at
    wiring time (and in CI via ``tools/check_metric_names.py``), not
    silently on a dashboard.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict[str, _Instrument] = {}
        # the registry's own cardinality-guard evidence: one family
        # label per instrument that ever collapsed a label value to
        # "other" (family names are bounded by the registrations)
        registry = self
        self._label_overflow = registry.counter(
            "telemetry.label_overflow",
            "label values collapsed to 'other' by the cardinality guard",
            label="family",
        )

    def _register(self, inst: _Instrument) -> _Instrument:
        with self._lock:
            if inst.name in self._instruments:
                raise ValueError(f"metric {inst.name!r} already registered")
            self._instruments[inst.name] = inst
            # wire the shared overflow counter into every value-owning
            # instrument (the counter itself guards via its own cap)
            inst._overflow = getattr(self, "_label_overflow", None)
        return inst

    def counter(self, name: str, help: str = "", *,
                fn=None, label=None,
                json_render: bool = True,
                max_label_values: int | None = None) -> Counter:
        return self._register(
            Counter(name, help, fn=fn, label=label,
                    json_render=json_render,
                    max_label_values=max_label_values)
        )

    def gauge(self, name: str, help: str = "", *,
              fn=None, label=None,
              json_render: bool = True,
              max_label_values: int | None = None) -> Gauge:
        return self._register(
            Gauge(name, help, fn=fn, label=label,
                  json_render=json_render,
                  max_label_values=max_label_values)
        )

    def histogram(self, name: str, help: str = "", *,
                  buckets: tuple = LATENCY_BUCKETS_MS,
                  label: str | None = None,
                  exemplars: bool = False,
                  max_label_values: int | None = None) -> Histogram:
        return self._register(Histogram(name, help, buckets=buckets,
                                        label=label, exemplars=exemplars,
                                        max_label_values=max_label_values))

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._instruments)

    def _snapshot(self) -> list[_Instrument]:
        with self._lock:
            return [self._instruments[k] for k in sorted(self._instruments)]

    # -- renderings ----------------------------------------------------------

    def render_json(self) -> dict:
        """Nested-by-dots snapshot: ``batcher.launcher.queued`` renders
        as ``{"batcher": {"launcher": {"queued": N}}}`` — the exact
        shape the old hand-assembled ``/metrics`` dict had, so
        dashboards and tests keep their keys."""
        out: dict = {}
        for inst in self._snapshot():
            if not inst.json_render:
                continue
            val = inst.collect()
            if val is None:
                continue
            if inst.kind == "histogram" and isinstance(val, dict):
                # unlabel single-series histograms for readability
                if set(val) == {""}:
                    val = val[""]
            elif (
                isinstance(val, dict)
                and val
                and isinstance(next(iter(val)), tuple)
            ):
                # multi-label series nest by label value:
                # {("g_variants", "5m"): 2.0} -> {"g_variants": {"5m": 2.0}}
                nested: dict = {}
                for key_tuple, v in val.items():
                    node = nested
                    for part in key_tuple[:-1]:
                        node = node.setdefault(str(part), {})
                    node[str(key_tuple[-1])] = v
                val = nested
            node = out
            parts = inst.name.split(".")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = val
        return out

    def render_prometheus(self, *, openmetrics: bool = False) -> str:
        """Prometheus text exposition. Dotted names flatten to
        underscores under the ``sbeacon_`` namespace. Exemplar
        annotations are only legal in the OpenMetrics dialect — the
        classic text format's parser rejects them — so they render
        only with ``openmetrics=True`` (which also appends the
        spec-required ``# EOF`` terminator)."""
        lines: list[str] = []
        for inst in self._snapshot():
            val = inst.collect()
            if val is None:
                continue
            pname = "sbeacon_" + inst.name.replace(".", "_")
            if inst.help:
                lines.append(f"# HELP {pname} {inst.help}")
            lines.append(f"# TYPE {pname} {inst.kind}")
            # OpenMetrics requires counter SAMPLES to be named
            # <family>_total (the TYPE line keeps the family name);
            # the classic format rejects the suffix form instead
            sname = (
                pname + "_total"
                if openmetrics and inst.kind == "counter"
                else pname
            )
            if inst.kind == "histogram":
                label = inst.label
                for key, series in sorted(val.items()):
                    base = f'{label}="{_esc(key)}",' if label and key else ""
                    exem = (
                        series.get("exemplars") or {}
                        if openmetrics
                        else {}
                    )
                    for le, cum in series["buckets"].items():
                        line = f'{pname}_bucket{{{base}le="{le}"}} {cum}'
                        ex = exem.get(le)
                        if ex is not None:
                            # OpenMetrics exemplar: the most recent
                            # observation that landed in this bucket,
                            # linked to its distributed trace
                            line += (
                                f' # {{trace_id="{_esc(ex["traceId"])}"}}'
                                f' {_num(ex["value"])} {ex["time"]:.3f}'
                            )
                        lines.append(line)
                    sfx = f"{{{base[:-1]}}}" if base else ""
                    lines.append(f"{pname}_sum{sfx} {series['sum']}")
                    lines.append(f"{pname}_count{sfx} {series['count']}")
            elif isinstance(val, dict):
                labels = inst.labels or ("key",)
                for key, v in sorted(val.items()):
                    vals = key if isinstance(key, tuple) else (key,)
                    lbl = ",".join(
                        f'{ln}="{_esc(str(lv))}"'
                        for ln, lv in zip(labels, vals)
                    )
                    lines.append(f"{sname}{{{lbl}}} {_num(v)}")
            else:
                lines.append(f"{sname} {_num(val)}")
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"


def _esc(s: str) -> str:
    return s.replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


def percentiles(xs) -> dict:
    """{p50, p95, p99} of a sample window (numpy-interpolated, 2dp),
    or {} when empty — the one summary shape every stage-timing
    producer (batcher, engine materialisation, runner admission wait)
    feeds into /debug/status."""
    xs = list(xs)
    if not xs:
        return {}
    import numpy as np

    a = np.asarray(xs)
    return {
        "p50": round(float(np.percentile(a, 50)), 2),
        "p95": round(float(np.percentile(a, 95)), 2),
        "p99": round(float(np.percentile(a, 99)), 2),
    }


def _num(v) -> str:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return "0"
    return f"{f:g}"


# -- per-request cost vector ---------------------------------------------------


class CostVector:
    """The resource cost ONE request incurred, accumulated additively
    by the instrumentation points along its path (ISSUE 11):

    - ``device_us`` — device-launch microseconds, pro-rated from the
      batcher's measured per-launch execute time to this request's
      share of the launch's query specs (serving.py);
    - ``host_rows`` — candidate rows walked by the numpy host matcher
      (``engine.host_match_rows`` — per-shard fallbacks, overflow
      paths, and the delta tail);
    - ``delta_shards`` — delta-tail shards walked for this query
      (the engine's per-shard host dispatch);
    - ``worker_rtt_ms`` — coordinator->worker round-trip time on
      successful ``/search`` legs (a worker was occupied that long on
      this request's behalf);
    - ``queue_wait_ms`` — time queued (fair-queue admission wait +
      micro-batch wait); contention, not resource cost, so it is
      excluded from the cost-unit scalar but attributed per tenant;
    - ``response_bytes`` — serialized response size;
    - ``cache`` — response-cache outcome (``hit`` / ``negative_hit`` /
      ``miss`` / ``""`` when the cache never saw the query).

    One vector rides each :class:`RequestContext`; charges without an
    ambient context fall into the process-global
    :data:`UNATTRIBUTED_COST` residue so the accounting plane can
    prove what fraction of measured work it attributed. Additive
    updates take one short lock — engine scatter threads and the
    batcher's fetcher thread charge the same vector concurrently.
    """

    NUMERIC = (
        "device_us",
        "host_rows",
        "delta_shards",
        "worker_rtt_ms",
        "queue_wait_ms",
        "response_bytes",
    )

    __slots__ = NUMERIC + ("cache", "_sealed", "_lock")

    def __init__(self):
        for f in self.NUMERIC:
            setattr(self, f, 0.0)
        self.cache = ""
        self._sealed = False
        self._lock = threading.Lock()

    def add(self, *, cache: str | None = None, **fields) -> None:
        """Accumulate numeric fields (and/or set the cache outcome).
        Unknown field names raise — a typo'd charge site must fail in
        tests, not silently leak cost. Charges landing AFTER the
        vector was :meth:`seal`-ed (the request already folded into
        the accounting table — e.g. a launch completing after its
        submitter 504ed, or a losing hedge leg's RTT) redirect to the
        unattributed residue, so they appear in the attribution
        DENOMINATOR instead of vanishing from both sides."""
        with self._lock:
            sealed = self._sealed
            if not sealed:
                for k, v in fields.items():
                    if k not in self.NUMERIC:
                        raise ValueError(f"unknown cost field {k!r}")
                    setattr(self, k, getattr(self, k) + float(v))
                if cache:
                    self.cache = cache
        if sealed and self is not UNATTRIBUTED_COST:
            UNATTRIBUTED_COST.add(cache=cache, **fields)

    def seal(self) -> None:
        """Mark the vector folded: later charges go to the residue."""
        with self._lock:
            self._sealed = True

    def snapshot(self) -> dict:
        with self._lock:
            out = {f: getattr(self, f) for f in self.NUMERIC}
            out["cache"] = self.cache
        return out

    def nonzero(self) -> bool:
        with self._lock:
            return bool(self.cache) or any(
                getattr(self, f) for f in self.NUMERIC
            )

    def as_dict(self) -> dict:
        """Compact rounded rendering for slow-query-log records and
        ``/debug/status`` — zero fields are dropped."""
        snap = self.snapshot()
        out = {}
        for f in self.NUMERIC:
            v = snap[f]
            if v:
                out[f] = round(v, 2)
        if snap["cache"]:
            out["cache"] = snap["cache"]
        return out


#: process-global residue: charges that land with NO ambient request
#: context (warmup launches, background drains, abandoned waiters)
#: accumulate here, so ``/ops/costs`` can report an attribution ratio
#: instead of silently dropping unowned work
UNATTRIBUTED_COST = CostVector()


def charge_cost(**fields) -> None:
    """Charge the current request's cost vector (ambient context), or
    the process-global unattributed residue when off-request. The
    no-context fast path is one thread-local read."""
    ctx = _ambient.ctx
    vec = ctx.cost if ctx is not None else UNATTRIBUTED_COST
    vec.add(**fields)


def charge_cost_to(ctx, **fields) -> None:
    """Charge an EXPLICIT request context's cost vector (pool threads
    holding a captured context, e.g. the batcher's fetcher stage);
    ``ctx=None`` charges the unattributed residue."""
    vec = ctx.cost if ctx is not None else UNATTRIBUTED_COST
    vec.add(**fields)


# -- request context / distributed tracing ------------------------------------

#: the cross-process trace header (coordinator->worker and client->API)
TRACE_HEADER = "X-Beacon-Trace"


def new_trace_id() -> str:
    """64-bit hex trace id (the Dapper convention's width)."""
    return uuid.uuid4().hex[:16]


#: acceptable inbound trace ids — anything else is replaced with a
#: fresh id, since the value is re-emitted into outbound worker HTTP
#: headers and log lines (no CRLF or unbounded junk pass-through)
_TRACE_ID_RE = re.compile(r"^[A-Za-z0-9_.\-]{1,64}$")


def sanitize_trace_id(raw: str | None) -> str | None:
    """``raw`` if it is a well-formed trace id, else None."""
    if raw and _TRACE_ID_RE.match(raw):
        return raw
    return None


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class RequestContext:
    """Ambient per-request identity: one trace id from ingress to every
    worker hop, plus an outcome-notes dict producers annotate (cache
    hit/miss, fused/mesh path, breaker trips) that the slow-query log
    snapshots. ``notes`` is copy-on-write (:func:`annotate` rebinds a
    fresh dict, never mutates in place), so a reader iterating its
    snapshot can never race a writer — an abandoned pool thread may
    still be annotating after the request returned. Two concurrent
    annotates may drop one note; acceptable for observability.

    ``stages`` (ISSUE 37) is the request's own stage vector, stage name
    -> milliseconds, fed by the stage clock (``utils/trace.py``) from
    the very reading that feeds the stage's sums and under the rule of
    its ``req_ms``: a scope on a thread this context is ambient on, a
    launch's scopes for each entry it serves, an observed hand-off at
    the site that knows whose it was. The chain's stages follow one
    another for a request, so ``stages`` has one writer at a time.
    ``beside`` holds what was done FOR the request while its own
    thread was parked (a fan-out's pool threads, ``tracer.serving(0)``):
    thread-milliseconds by stage, never part of the chain; its writers
    are concurrent, and two adds that race may drop one, as notes do.
    Wall only: no CPU is read per request."""

    __slots__ = (
        "trace_id", "route", "t_start", "notes", "cost", "plan",
        "explain", "stages", "beside",
    )

    def __init__(self, trace_id: str | None = None, route: str = ""):
        self.trace_id = trace_id or new_trace_id()
        self.route = route
        self.t_start = time.perf_counter()
        self.notes: dict = {}
        #: the request's resource-cost vector (ISSUE 11): created
        #: eagerly so concurrent charge sites never race an install
        self.cost = CostVector()
        #: the request's execution-plan stage list (ISSUE 19):
        #: plan.plan_stage appends bounded entries; created eagerly
        #: like the cost vector so producers never race an install
        self.plan: list = []
        #: True when the API layer authorized ?explain=1 — the engine's
        #: cache front bypasses the response cache for explained
        #: requests (plan.explain_active)
        self.explain = False
        # defaultdict: ``vec[name] += ms`` is one subscript, one add and
        # one store, with no call between them for a thread switch
        self.stages: dict = collections.defaultdict(float)
        self.beside: dict = collections.defaultdict(float)

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self.t_start) * 1e3


class _Ambient(threading.local):
    # a class default: a thread that never scoped a request reads None
    # without raising inside getattr (every stage scope reads it)
    ctx = None


_ambient = _Ambient()


def current_context() -> RequestContext | None:
    """The request context the API layer scoped onto this thread (or
    None). Pool workers re-install the submitting request's context via
    :func:`request_context`, exactly like ambient deadlines."""
    return _ambient.ctx


@contextmanager
def request_context(ctx: RequestContext | None):
    """Install ``ctx`` as this thread's ambient request context
    (``None`` restores 'no context' — safe to pass through)."""
    prev = _ambient.ctx
    _ambient.ctx = ctx
    try:
        yield ctx
    finally:
        _ambient.ctx = prev


#: the literal registry of every outcome-note key producers may
#: ``annotate(...)`` — the slow-query log's schema, in effect. The
#: static lint ``tools/check_annotation_keys.py`` (tier-1 via
#: tests/test_telemetry.py) enforces two-way parity between this set
#: and the annotate() call sites, exactly like the metric-name lint:
#: an unregistered key is an invisible note, a registered-but-unused
#: key is a dashboard field that silently flatlined.
ANNOTATION_KEYS = frozenset({
    "batch_index",
    "batch_ms",
    "breaker",
    "dispatch",
    "dispatch_l0",
    "dispatch_tier",
    "failover",
    "granularity",
    "lane",
    "query_job",
    "replica_hedge",
    "response_cache",
    "short_circuit",
    "tenant",
    "unavailable_datasets",
})


def annotate(**kw) -> None:
    """Attach outcome notes (``response_cache="hit"``, ``path="fused"``)
    to the current request, if any — a no-op off-request, so producers
    call it unconditionally. Copy-on-write rebind: the previous notes
    dict is never mutated, so concurrent readers (the slow-query log
    snapshotting a request an abandoned pool thread still annotates)
    cannot crash mid-iteration."""
    ctx = _ambient.ctx
    if ctx is not None:
        ctx.notes = {**ctx.notes, **kw}


def stage_notes(ctx: RequestContext, suffix: str = "") -> dict:
    """``{"stages": ..., "beside": ...}`` of one request for a record a
    person reads (the slow-query log; ``?explain=1``, with ``suffix``
    ``Ms`` beside the plan's own ``stages``): milliseconds rounded to
    the microsecond, zeros dropped, an empty vector left out."""
    out = {}
    for key in ("stages", "beside"):
        vec = {
            name: ms
            for name, v in list(getattr(ctx, key).items())
            if (ms := round(v, 3))
        }
        if vec:
            out[key + suffix] = vec
    return out


# -- slow-query log -----------------------------------------------------------


class SlowQueryLog:
    """Structured slow-request record: any request whose latency tops
    ``threshold_ms`` emits one JSON line (trace id, route, status,
    elapsed, outcome notes) to the ``sbeacon.slowquery`` logger (and an
    optional file) and lands in a bounded in-memory ring for ``/_trace``
    adjacency. ``threshold_ms < 0`` disables; ``0`` records everything
    (debug). The fast path for a request under threshold is one float
    compare."""

    def __init__(self, threshold_ms: float = 1000.0, *,
                 keep: int = 256, path: str = ""):
        self.threshold_ms = float(threshold_ms)
        self.path = path
        self._keep = max(1, keep)
        self._lock = threading.Lock()
        self._ring: list[dict] = []
        self._count = 0
        self._logger = logging.getLogger("sbeacon.slowquery")

    def count(self) -> int:
        with self._lock:
            return self._count

    def recent(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def records(self, elapsed_ms: float) -> bool:
        """Whether a request of this latency is recorded (what a caller
        asks before it builds notes only a record needs)."""
        return 0 <= self.threshold_ms <= elapsed_ms

    def maybe_record(self, *, trace_id: str, route: str, status: int,
                     elapsed_ms: float, notes: dict | None = None) -> bool:
        if not self.records(elapsed_ms):
            return False
        entry = {
            "traceId": trace_id,
            "route": route,
            "status": int(status),
            "elapsedMs": round(elapsed_ms, 2),
            "thresholdMs": self.threshold_ms,
            "time": time.time(),
        }
        if notes:
            entry["notes"] = dict(notes)
        line = json.dumps(entry, sort_keys=True, default=str)
        with self._lock:
            self._count += 1
            self._ring.append(entry)
            if len(self._ring) > self._keep:
                del self._ring[: -self._keep]
        self._logger.warning("%s", line)
        if self.path:
            try:
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            except OSError:  # a full disk must not fail the request
                log.exception("slow-query log write failed")
        return True


# -- flight recorder (control-plane event journal) ----------------------------


class EventJournal:
    """Bounded structured journal of control-plane transitions — the
    flight recorder. Breaker opens/closes, replica failovers, hedges,
    rediscovery passes, route-table publishes, cache invalidations,
    fused-stack rebuilds and admission sheds each
    publish ONE small event here, stamped with monotonic time (ordering
    survives wall
    clock jumps), wall time (human correlation) and the ambient trace
    id when the transition happened inside a request. ``/ops/events``
    serves the ring with ``since``/``kind`` filters, so "what did the
    control plane just do and to whom" is one query instead of a log
    dig.

    Publishing is O(1): one lock, one deque append — safe to call from
    breaker/dispatch hot paths. The ring holds the last ``keep``
    events; ``published()`` counts lifetime publishes so a consumer
    can detect it missed events that already rolled off.
    """

    def __init__(self, keep: int = 1024, *, enabled: bool = True,
                 clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.enabled = bool(enabled)
        self._keep = max(1, int(keep))
        self._ring: "collections.deque[dict]" = collections.deque(
            maxlen=self._keep
        )
        self._seq = 0
        self._published = 0

    def configure(self, *, keep: int | None = None,
                  enabled: bool | None = None) -> None:
        """Apply config-tier settings to an already-constructed journal
        (the process-global one is built at import from env defaults;
        ObservabilityConfig re-applies through the app)."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if keep is not None and max(1, int(keep)) != self._keep:
                self._keep = max(1, int(keep))
                self._ring = collections.deque(
                    self._ring, maxlen=self._keep
                )

    def publish(self, kind: str, **data) -> int | None:
        """Record one event; returns its sequence number (None when the
        journal is disabled). ``data`` values must be JSON-safe — the
        event is served verbatim at ``/ops/events``."""
        if not self.enabled:
            return None
        evt: dict = {"kind": kind, "tMono": round(self._clock(), 6),
                     "time": time.time()}
        ctx = current_context()
        if ctx is not None:
            evt["traceId"] = ctx.trace_id
        if data:
            evt["data"] = data
        with self._lock:
            self._seq += 1
            self._published += 1
            evt["seq"] = self._seq
            self._ring.append(evt)
        return evt["seq"]

    @staticmethod
    def _kind_matcher(kind: str):
        """``kind`` is a COMMA-SEPARATED list of filters, each
        matching exactly or by prefix (``breaker`` matches
        ``breaker.open``) — one parser for BOTH the newest-capped and
        the paginated read paths, so their filter semantics can never
        diverge."""
        kinds = [k.strip() for k in kind.split(",") if k.strip()]

        def _match(k: str) -> bool:
            return not kinds or any(
                k == want or k.startswith(want + ".") for want in kinds
            )

        return _match

    def events(self, *, since: int = 0, kind: str = "",
               limit: int = 256) -> list[dict]:
        """Events with seq > ``since``, newest last, optionally
        filtered by kind (comma-separated exact-or-prefix list — an
        operator correlating two control planes tails ONE interleaved
        stream), capped at the most recent ``limit``."""
        _match = self._kind_matcher(kind)
        with self._lock:
            evs = [
                dict(e)
                for e in self._ring
                if e["seq"] > since and _match(e["kind"])
            ]
        limit = int(limit)
        return evs[-limit:] if limit > 0 else []

    def events_page(
        self, *, since: int = 0, kind: str = "", limit: int = 256
    ) -> tuple[list[dict], int]:
        """Forward pagination for tailing clients (ISSUE 12 satellite):
        the OLDEST ``limit`` matching events with seq > ``since`` plus a
        ``nextSince`` cursor — pass it back as ``since`` to resume with
        no re-reads and no silently skipped middle (the newest-capped
        :meth:`events` drops a burst's middle entries, so a tailer had
        to guess the next monotonic stamp). When the page is truncated
        the cursor is the last returned seq (more pages follow); when
        the caller is caught up it jumps to the journal head, so
        filtered tails skip non-matching events instead of rescanning
        them every poll. Entries that rolled off the ring during the
        client's gap are gone either way — ``published()`` vs the count
        consumed detects that loss."""
        _match = self._kind_matcher(kind)
        limit = int(limit)
        if limit <= 0:
            return [], int(since)
        page: list[dict] = []
        truncated = False
        with self._lock:
            # stop at limit+1 matches: a far-behind tailer must cost a
            # page's worth of copies under the lock, not a full-ring
            # copy discarded down to `limit` (publish_event contends
            # on this lock from control-plane hot paths)
            for e in self._ring:
                if e["seq"] > since and _match(e["kind"]):
                    if len(page) == limit:
                        truncated = True
                        break
                    page.append(dict(e))
            head = self._seq
        if truncated:  # resume right after this page
            return page, page[-1]["seq"]
        return page, max(int(since), head)

    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    def published(self) -> int:
        with self._lock:
            return self._published


def _env_journal() -> EventJournal:
    from .config import ENV_OFF

    size = os.environ.get("BEACON_EVENT_JOURNAL_SIZE", "") or "1024"
    enabled = os.environ.get(
        "BEACON_EVENT_JOURNAL_ENABLED", ""
    ).lower() not in ENV_OFF
    try:
        keep = int(size)
    except ValueError:
        keep = 1024
    return EventJournal(keep=keep, enabled=enabled)


#: the process flight recorder: control-plane sites publish here via
#: :func:`publish_event`; ``/ops/events`` serves it. Process-global
#: like ``profiler`` — breakers/routers live below the app layer and
#: must not need a registry reference to be observable.
journal = _env_journal()


def publish_event(kind: str, **data) -> int | None:
    """Publish one control-plane event to the process journal."""
    return journal.publish(kind, **data)


# -- device-plane flight recorder ---------------------------------------------


#: the compiled-program families the device plane dispatches: the
#: scatter tile kernels, the XLA gather kernel (single-shard and fused
#: stacked alike — one program family), the delta-tail L0 mini-index
#: (same kernel, its own family so tail serving is attributable), the
#: engine's mesh-stack program (``mesh``: ``parallel/mesh.sharded_query``,
#: one launch a multi-dataset request over the dataset-sharded stack),
#: and the genotype-plane program. Every launch record names exactly
#: one of these.
DEVICE_FAMILIES = (
    "scatter",
    "fused",
    "fused_l0",
    "mesh",
    "plane",
)


#: the jitted functions behind a family's launches, by the names the
#: profiler shows them under (``jit_<name>`` on a device's ``XLA
#: Modules`` line). ``benchmark/rooflines/<family>.py`` finds a family's
#: device time by these; tests/test_stages.py holds the two together, so
#: a rename fails a test instead of dropping ``<family>_kernel_ms``.
DEVICE_PROGRAMS = {
    "scatter": ("_scatter_batch", "_scatter_many"),
    "plane": ("_selected_batch",),
    "fused": ("_query_batch_impl",),
    "fused_l0": ("_query_batch_impl",),
    "mesh": ("_local_query",),
}


class DeviceFlightRecorder:
    """Per-launch telemetry for every compiled device program — the
    device-plane twin of the control plane's :class:`EventJournal`
    (ISSUE 14).

    The reference gets per-invocation visibility for free (every
    Lambda in its scatter-gather is individually metered by
    CloudWatch); our replacement for that fan-out — the micro-batcher's
    compiled launches, the fused stack, the mesh program — used
    to count launches in UNLOCKED module globals (``mesh.N_LAUNCHES``
    ``+= 1`` raced across request threads on real accelerators, where
    no ``_CPU_COLLECTIVE_LOCK`` serialises launches) and recorded
    nothing else. This recorder is the single seam all kernel families
    report through:

    - a bounded **launch ring**: program family, batch tier,
      real-vs-padded spec counts (padding-waste ratio), evaluated
      (device, query) pairs, encode/launch/fetch ms, and the ambient
      trace id per launch;
    - lifetime **counters** under one lock (the old module names stay
      readable as module properties backed by these);
    - a **compile-event tracker**: the first launch of a novel
      (program, shape) key is a compile — its wall duration is
      stamped, and a compile observed OUTSIDE a warmup phase emits a
      ``device.compile`` journal event and ticks
      ``device.mid_request_compiles`` (the config9-era "fresh program
      per novel batch size" soak-tail regression becomes a named,
      alertable signal instead of a latency mystery).

    Everything is O(1) per launch (one short lock, dict upserts) and
    every read surface snapshots under the same short lock — never an
    engine or stack-rebuild lock — so ``/device/status`` answers while
    a mesh rebuild is in flight.
    """

    def __init__(self, ring_size: int = 256, *,
                 compile_tracking: bool = True):
        self._lock = threading.Lock()
        self._keep = max(1, int(ring_size))
        self._ring: "collections.deque[dict]" = collections.deque()
        self._by_seq: dict[int, dict] = {}
        self._seq = 0
        self.compile_tracking = bool(compile_tracking)
        # lifetime counters: per family, per seam (the module-property
        # back-compat views), evaluated pairs
        self._families: dict[str, int] = {}
        # the (query, dataset) slots launches carried that a request
        # asked, by family: over _families, the targets of one launch
        self._targets: dict[str, int] = {}
        # launches of one-chip programs by the chip they ran on
        self._chips: dict[str, int] = {}
        self._seams: dict[str, int] = {}
        self._pairs = 0
        # padding accounting: family -> [real, padded] spec slots, and
        # (family, tier) -> [real, padded] for the tier-boundary view
        self._pad: dict[str, list] = {}
        self._pad_tier: dict[tuple, list] = {}
        # output-diet accounting (ISSUE 17): bytes actually
        # materialised on host by result fetches, and encode buffers
        # donated to their launch instead of double-buffered in HBM
        self._fetched_bytes = 0
        self._donated = 0
        # bytes plane-family launches read from the resident planes
        self._gathered_bytes = 0
        # host arrays put on the device for launches' query batches,
        # by family
        self._uploads: dict[str, int] = {}
        # compile tracker: first-seen (program, shape) keys
        self._compiles: dict[str, dict] = {}
        self._warmup_depth = 0
        self._mid_request = 0
        self._last_mid: dict | None = None
        # device paths that failed and were served from the host (or a
        # slower device path) instead, by code site — the answer stays
        # right, so this count is the only place the failure shows
        self._fallbacks: dict[str, int] = {}

    def configure(self, *, ring_size: int | None = None,
                  compile_tracking: bool | None = None) -> None:
        """Apply config-tier settings to the process-global recorder
        (built at import from env defaults, like :data:`journal`)."""
        with self._lock:
            if compile_tracking is not None:
                self.compile_tracking = bool(compile_tracking)
            if ring_size is not None:
                self._keep = max(1, int(ring_size))
                while len(self._ring) > self._keep:
                    old = self._ring.popleft()
                    self._by_seq.pop(old["seq"], None)

    @contextmanager
    def warmup_phase(self):
        """Mark compiles as EXPECTED while a warmup runs (the engine's
        program pre-compilation). A process-wide depth
        counter, not a thread-local flag: warmup launches ride the
        batcher's pool threads, so the compiling thread is not the
        thread that entered warmup."""
        with self._lock:
            self._warmup_depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._warmup_depth -= 1

    # -- the one write seam ---------------------------------------------------

    def record_launch(
        self,
        family: str,
        *,
        seam: str,
        tier: int,
        specs_real: int,
        specs_padded: int,
        evaluated_pairs: int = 0,
        launch_ms: float = 0.0,
        program_key=None,
        donated: int = 0,
        uploads: int = 0,
        chip: int | None = None,
        targets: int | None = None,
    ) -> int:
        """Record ONE device launch; returns its sequence number (the
        handle :meth:`note_stage` later attaches encode/fetch timings
        to). ``seam`` is the dispatching module (``kernel`` / ``mesh``
        / ``scatter`` — the back-compat module properties read these);
        ``program_key`` is a hashable (program, shape) identity fed to
        the compile tracker (None skips tracking for this launch);
        ``chip`` is the device a one-chip program ran on (None for a
        program that spans the mesh); ``uploads`` the host arrays put
        on the device for this launch's query batch (the seams that
        count them: ``fused``, ``fused_l0``, ``mesh``); ``targets`` the
        (query, dataset) pairs it answers where that is not its real
        specs (``device.launch_targets``)."""
        specs_real = int(specs_real)
        specs_padded = max(int(specs_padded), specs_real, 1)
        rec: dict = {
            "family": family,
            "tier": int(tier),
            "specs": specs_real,
            "padded": specs_padded,
            "padWaste": round(1.0 - specs_real / specs_padded, 4),
            "evaluatedPairs": int(evaluated_pairs),
            "launchMs": round(float(launch_ms), 3),
            "time": time.time(),
        }
        if donated:
            rec["donated"] = int(donated)
        if uploads:
            rec["uploads"] = int(uploads)
        ctx = current_context()
        if ctx is not None:
            rec["traceId"] = ctx.trace_id
        compile_evt = None
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._families[family] = self._families.get(family, 0) + 1
            self._targets[family] = self._targets.get(family, 0) + int(
                specs_real if targets is None else targets
            )
            if chip is not None:
                self._chips[str(chip)] = self._chips.get(str(chip), 0) + 1
            self._seams[seam] = self._seams.get(seam, 0) + 1
            self._pairs += int(evaluated_pairs)
            self._donated += int(donated)
            if uploads:
                self._uploads[family] = (
                    self._uploads.get(family, 0) + int(uploads)
                )
            pad = self._pad.setdefault(family, [0, 0])
            pad[0] += specs_real
            pad[1] += specs_padded
            ptier = self._pad_tier.setdefault((family, int(tier)), [0, 0])
            ptier[0] += specs_real
            ptier[1] += specs_padded
            if program_key is not None:
                entry = self._track_compile_locked(
                    family, tier, program_key, launch_ms, rec["time"]
                )
                if entry is not None:
                    rec["compiled"] = True
                    if not entry["warmup"]:
                        compile_evt = entry
            self._ring.append(rec)
            self._by_seq[rec["seq"]] = rec
            while len(self._ring) > self._keep:
                old = self._ring.popleft()
                self._by_seq.pop(old["seq"], None)
        if compile_evt is not None:
            # outside the recorder lock: the journal has its own, and a
            # mid-request compile inside a request carries its trace id
            publish_event(
                "device.compile",
                program=family,
                shape=compile_evt["key"],
                tier=compile_evt["tier"],
                durationMs=compile_evt["durationMs"],
            )
        return rec["seq"]

    def _track_compile_locked(
        self, family, tier, program_key, duration_ms, when
    ) -> dict | None:
        """The tracker entry when ``program_key`` is first seen (held
        under ``_lock``), else None."""
        if not self.compile_tracking:
            return None
        key = self._key_str(program_key)
        if key in self._compiles:
            return None
        entry = {
            "key": key,
            "family": family,
            "tier": int(tier),
            "durationMs": round(float(duration_ms), 3),
            "time": when,
            "warmup": self._warmup_depth > 0,
        }
        self._compiles[key] = entry
        if not entry["warmup"]:
            self._mid_request += 1
            self._last_mid = entry
        return entry

    def record_compile(
        self, family: str, *, tier: int, program_key,
        duration_ms: float = 0.0,
    ) -> None:
        """Tell the compile tracker about a program that was compiled
        WITHOUT a recorded launch — a warmup loop that calls the jitted
        entry directly with all-padding slots. Counting those as
        launches would put pure padding into the launch and pad-waste
        series; not telling the tracker makes every first serving
        launch of a warmed program read as a mid-request compile."""
        with self._lock:
            entry = self._track_compile_locked(
                family, tier, program_key, duration_ms, time.time()
            )
        if entry is not None and not entry["warmup"]:
            publish_event(
                "device.compile",
                program=family,
                shape=entry["key"],
                tier=entry["tier"],
                durationMs=entry["durationMs"],
            )

    @staticmethod
    def _key_str(program_key) -> str:
        if isinstance(program_key, str):
            return program_key
        if isinstance(program_key, (tuple, list)):
            return ":".join(str(p) for p in program_key)
        return str(program_key)

    def note_stage(self, seq: int, *, encode_ms: float | None = None,
                   fetch_ms: float | None = None,
                   fetch_bytes: int | None = None,
                   gather_bytes: int | None = None) -> None:
        """Attach a stage timing to a recorded launch (the encode
        happens before dispatch on the submitting thread, the fetch
        after it on the fetcher thread — neither is known at
        :meth:`record_launch` time). The per-record annotation no-ops
        once the record has rolled off the ring, but ``fetch_bytes``
        still accumulates into the lifetime counter — ring eviction
        must not leak fetched bytes out of ``device.fetched_bytes``.
        ``gather_bytes`` (``plane`` family) is what the launch read
        from the resident genotype planes: whole blocks of the rows its
        queries matched, known once their counts are back."""
        with self._lock:
            if fetch_bytes is not None:
                self._fetched_bytes += int(fetch_bytes)
            if gather_bytes is not None:
                self._gathered_bytes += int(gather_bytes)
            rec = self._by_seq.get(seq)
            if rec is None:
                return
            if encode_ms is not None:
                rec["encodeMs"] = round(float(encode_ms), 3)
            if fetch_ms is not None:
                rec["fetchMs"] = round(float(fetch_ms), 3)
            if fetch_bytes is not None:
                rec["fetchBytes"] = int(fetch_bytes)
            if gather_bytes is not None:
                rec["gatherBytes"] = int(gather_bytes)

    def record_fallback(self, site: str) -> None:
        """Count ONE device-path failure that was absorbed by a host
        (or slower device) path at ``site`` — a literal label from the
        DEPLOYMENT.md ``device.fallbacks`` row, so the series stays
        bounded."""
        with self._lock:
            self._fallbacks[site] = self._fallbacks.get(site, 0) + 1

    def fallbacks_by_site(self) -> dict:
        with self._lock:
            return dict(self._fallbacks)

    # -- back-compat module-property views ------------------------------------

    @property
    def kernel_launches(self) -> int:
        """XLA gather-kernel launches (the old ``kernel.N_LAUNCHES``)."""
        with self._lock:
            return self._seams.get("kernel", 0)

    @property
    def mesh_launches(self) -> int:
        """Mesh shard_map launches (the old ``mesh.N_LAUNCHES``)."""
        with self._lock:
            return self._seams.get("mesh", 0)

    @property
    def scatter_dispatches(self) -> int:
        """Scatter tile-kernel dispatches (``scatter_kernel.N_DISPATCHES``)."""
        with self._lock:
            return self._seams.get("scatter", 0)

    @property
    def evaluated_pairs(self) -> int:
        with self._lock:
            return self._pairs

    @property
    def fetched_bytes(self) -> int:
        """Lifetime bytes result fetches materialised on host — the
        owner-sharded output diet's structural evidence (ISSUE 17)."""
        with self._lock:
            return self._fetched_bytes

    @property
    def plane_gather_bytes(self) -> int:
        """Lifetime bytes ``plane``-family launches gathered from the
        resident genotype planes (``ops.plane_kernel.reduce_rows``):
        over ``device.launches{plane}``, what a launch reads."""
        with self._lock:
            return self._gathered_bytes

    @property
    def donated_buffers(self) -> int:
        """Lifetime encode buffers donated to their launch instead of
        double-buffered in HBM (the upload-path donation seam)."""
        with self._lock:
            return self._donated

    # -- read surfaces --------------------------------------------------------

    def launches_by_family(self) -> dict:
        with self._lock:
            return dict(self._families)

    def launch_targets_by_family(self) -> dict:
        """{family: (query, dataset) slots its launches carried for a
        request}: over ``launches_by_family``, the targets one launch
        answers (16 where a request over sixteen cohorts of one chip
        rides one ``plane`` launch, 1 where each is launched alone)."""
        with self._lock:
            return dict(self._targets)

    def launches_by_chip(self) -> dict:
        with self._lock:
            return dict(self._chips)

    def query_uploads_by_family(self) -> dict:
        """{family: host arrays put on the device for its launches'
        query batches}: over ``launches_by_family`` it is the
        transfers a launch makes (1 since the packed upload)."""
        with self._lock:
            return dict(self._uploads)

    def _pad_waste_by_family_locked(self) -> dict:
        return {
            f: round(1.0 - real / padded, 4)
            for f, (real, padded) in self._pad.items()
            if padded
        }

    def pad_waste_by_family(self) -> dict:
        """{family: lifetime padding-waste ratio} — wasted pad slots
        over total padded slots."""
        with self._lock:
            return self._pad_waste_by_family_locked()

    def _worst_pad_waste_locked(self) -> dict | None:
        worst = None
        for (family, tier), (real, padded) in self._pad_tier.items():
            if not padded:
                continue
            waste = 1.0 - real / padded
            if worst is None or waste > worst[0]:
                worst = (waste, family, tier)
        if worst is None:
            return None
        return {
            "family": worst[1],
            "tier": worst[2],
            "waste": round(worst[0], 4),
        }

    def worst_pad_waste(self) -> dict | None:
        """The worst (family, tier) padding-waste cell, or None before
        any launch — ``/debug/status`` diagnosis material."""
        with self._lock:
            return self._worst_pad_waste_locked()

    def pad_tier_histogram(self) -> dict:
        """{(family, tier): (real, padded)} spec-slot totals — the
        traffic histogram ``ops.kernel.TierLadder.fit`` reads to split
        wasteful rungs (ISSUE 17)."""
        with self._lock:
            return {
                k: (int(v[0]), int(v[1]))
                for k, v in self._pad_tier.items()
            }

    def mid_request_compiles(self) -> int:
        with self._lock:
            return self._mid_request

    def last_mid_request_compile(self) -> dict | None:
        with self._lock:
            return dict(self._last_mid) if self._last_mid else None

    def _compile_snapshot_locked(self) -> dict:
        entries = [dict(e) for e in self._compiles.values()]
        return {
            "enabled": self.compile_tracking,
            "programs": len(entries),
            "midRequestCompiles": self._mid_request,
            "lastMidRequestCompile": (
                dict(self._last_mid) if self._last_mid else None
            ),
            "warmupShapes": sorted(
                e["key"] for e in entries if e["warmup"]
            ),
            "entries": sorted(entries, key=lambda e: e["time"]),
        }

    def compile_snapshot(self) -> dict:
        """The compile cache contents vs the warmup shape set."""
        with self._lock:
            return self._compile_snapshot_locked()

    def launch_summary(self) -> dict:
        """The compact rollup (no ring) ``/debug/status`` embeds."""
        with self._lock:
            total = sum(self._families.values())
            by_family = dict(self._families)
            targets = dict(self._targets)
            pairs = self._pairs
            uploads = dict(self._uploads)
            gathered = self._gathered_bytes
        return {
            "total": total,
            "byFamily": by_family,
            "targetsByFamily": targets,
            "evaluatedPairs": pairs,
            "queryUploads": uploads,
            "planeGatherBytes": gathered,
        }

    def snapshot(self) -> dict:
        """The full ``/device/status`` launch document: counters, the
        ring (oldest first), padding waste by family and tier, and the
        compile cache — assembled under ONE lock hold, so the ring and
        the counters describe the same instant (a launch landing
        between two separate acquisitions would break the
        ring-vs-counter reconciliation the golden test asserts). Never
        a stack or publish lock."""
        with self._lock:
            ring = [dict(r) for r in self._ring]
            keep = self._keep
            seq = self._seq
            families = dict(self._families)
            targets = dict(self._targets)
            pairs = self._pairs
            fetched = self._fetched_bytes
            gathered = self._gathered_bytes
            donated = self._donated
            uploads = dict(self._uploads)
            by_family = self._pad_waste_by_family_locked()
            by_tier = {
                f"{family}:{tier}": round(1.0 - real / padded, 4)
                for (family, tier), (real, padded)
                in sorted(self._pad_tier.items())
                if padded
            }
            worst = self._worst_pad_waste_locked()
            compiles = self._compile_snapshot_locked()
            fallbacks = dict(self._fallbacks)
        return {
            "total": sum(families.values()),
            "byFamily": families,
            "targetsByFamily": targets,
            "fallbacks": fallbacks,
            "evaluatedPairs": pairs,
            "fetchedBytes": fetched,
            "planeGatherBytes": gathered,
            "donatedBuffers": donated,
            "queryUploads": uploads,
            "ring": {"size": keep, "recorded": seq, "entries": ring},
            "padWaste": {
                "byFamily": by_family,
                "byTier": by_tier,
                "worst": worst,
            },
            "compiles": compiles,
        }

def _env_flight_recorder() -> DeviceFlightRecorder:
    from .config import ENV_OFF

    raw = os.environ.get("BEACON_DEVICE_RING_SIZE", "") or "256"
    try:
        ring = int(raw)
    except ValueError:
        ring = 256
    tracking = os.environ.get(
        "BEACON_COMPILE_TRACKING", ""
    ).lower() not in ENV_OFF
    return DeviceFlightRecorder(ring, compile_tracking=tracking)


#: the process device-plane flight recorder. Process-global like
#: ``journal`` — the kernel modules live below the app layer and must
#: not need a registry reference to be observable.
flight_recorder = _env_flight_recorder()


def record_device_launch(family: str, **kw) -> int:
    """Record one device launch on the process flight recorder (the
    kernel seams call this; reading the global at call time keeps the
    recorder swappable in tests)."""
    return flight_recorder.record_launch(family, **kw)


def record_device_compile(family: str, **kw) -> None:
    """Feed the compile tracker for a program compiled without a
    recorded launch (see :meth:`DeviceFlightRecorder.record_compile`)."""
    flight_recorder.record_compile(family, **kw)


def note_device_stage(seq, **kw) -> None:
    """Attach encode/fetch ms to a recorded launch; seq=None no-ops."""
    if seq is not None:
        flight_recorder.note_stage(seq, **kw)


def device_warmup_phase():
    """``with device_warmup_phase(): engine.warmup()`` — compiles
    inside the scope are expected, not mid-request regressions."""
    return flight_recorder.warmup_phase()


def record_device_fallback(site: str) -> None:
    """Tick ``device.fallbacks{site}``: a device path failed at
    ``site`` and the request/ingest was served another way. Every
    handler that absorbs such a failure calls this next to its log
    line, so a server whose kernels do not compile cannot look
    healthy."""
    flight_recorder.record_fallback(site)


def register_device_metrics(registry) -> None:
    """The device-plane series, callback-backed off the process
    recorder (the usual app fallback registration: call once per
    registry; producers keep no registry reference)."""
    registry.counter(
        "device.launches",
        "compiled device-program launches by family (scatter / fused "
        "/ fused_l0 / mesh / plane)",
        label="family",
        fn=lambda: flight_recorder.launches_by_family(),
    )
    registry.counter(
        "device.launch_targets",
        "(query, dataset) slots the launches of a family carried for "
        "a request (padding slots not counted); over device.launches "
        "of the same family, the targets one launch answers: a request "
        "over sixteen plane datasets of one chip is ONE plane launch "
        "of sixteen",
        label="family",
        fn=lambda: flight_recorder.launch_targets_by_family(),
    )
    registry.counter(
        "device.launches_by_chip",
        "launches of one-chip programs (scatter / plane / fused "
        "families) by the chip whose resident arrays they read; mesh "
        "programs, which run on every chip at once, are not counted",
        label="chip",
        fn=lambda: flight_recorder.launches_by_chip(),
    )
    registry.counter(
        "device.evaluated_pairs",
        "evaluated (device, query-slot) pairs summed over all mesh "
        "launches, and (query, dataset) pairs over all fused-stack "
        "launches — the per-device FLOP proxy",
        fn=lambda: flight_recorder.evaluated_pairs,
    )
    registry.gauge(
        "device.pad_waste",
        "lifetime padding-waste ratio by program family (padded spec "
        "slots never carrying a real query / total padded slots)",
        label="family",
        fn=lambda: flight_recorder.pad_waste_by_family(),
    )
    registry.counter(
        "device.mid_request_compiles",
        "device-program compiles observed OUTSIDE a warmup phase (a "
        "novel batch shape paid its XLA compile inside a request)",
        fn=lambda: flight_recorder.mid_request_compiles(),
    )
    registry.counter(
        "device.query_uploads",
        "host arrays put on the device for a launch's query batch, by "
        "family (fused / fused_l0 / mesh): over device.launches of the "
        "same family, the transfers a launch makes (1: one packed array)",
        label="family",
        fn=lambda: flight_recorder.query_uploads_by_family(),
    )
    registry.counter(
        "device.fallbacks",
        "device paths that failed and were served from the host (or a "
        "slower device path) instead, by code site — answers stay "
        "right, so a healthy deployment holds every site at zero",
        label="site",
        fn=lambda: flight_recorder.fallbacks_by_site(),
    )
    registry.counter(
        "device.fetched_bytes",
        "bytes result fetches materialised on host across all kernel "
        "families (the owner-sharded output diet's structural metric)",
        fn=lambda: flight_recorder.fetched_bytes,
    )
    registry.counter(
        "device.plane_gather_bytes",
        "bytes plane-family launches gathered from the resident "
        "genotype planes: blocks of eight rows, the rows their queries "
        "matched alone, 4 x lane words a row and plane read; over "
        "device.launches{plane}, what one launch reads",
        fn=lambda: flight_recorder.plane_gather_bytes,
    )
    registry.counter(
        "device.donated_buffers",
        "encoded query-batch buffers donated to their launch instead "
        "of double-buffered in HBM (BEACON_DONATE_UPLOADS)",
        fn=lambda: flight_recorder.donated_buffers,
    )
