"""Flag-gated hierarchical tracing / profiling.

The reference's observability is a compile-gated C++ stopwatch
(reference: lambda/summariseSlice/source/stopwatch.h, enabled by
``#define INCLUDE_STOP_WATCH`` at main.cpp:33 with throughput prints at
main.cpp:238-241), a ``timeit`` decorator in the latency harness
(simulations/test.py:16-23), and print-to-CloudWatch logging everywhere
else — SURVEY.md §5 calls for proper timers around kernels and host RPC
spans, kept flag-gated so the hot path pays nothing when disabled.

Design: one process-global :class:`Tracer` holding a thread-local span
stack. ``span("name")`` is a context manager (use ``tracer.wrap(name)``
for the decorator form); nested spans record parent-child structure.
When disabled (the default, like the reference's undefined
INCLUDE_STOP_WATCH) ``span`` returns a no-op singleton — no allocation,
no clock read. Enable via ``SBEACON_TRACE=1``, ``tracer.enable()``, or
the thread-scoped ``enabled(True)`` override. Finished spans aggregate
into per-name statistics (count / total / min / max) and retain the
most recent N complete span trees; ``report()`` renders both, and a
process enabled via ``SBEACON_TRACE=1`` prints the report to stderr at
exit (the stopwatch-print role of reference main.cpp:238-241).

Stages (ISSUE 24) are the part that is never off. ``stage(name)`` times
one boundary of the serving path, named in the literal registry
:data:`STAGES`, with ONE pair of clock reads that feeds every sink: the
stage's monotone ``count`` / ``sum_ms`` / ``req_ms`` and its bounded
ring (always), beside them the thread's own CPU clock into ``cpu_ms``
(ISSUE 36: what the scope cost the processor, where ``sum_ms`` also
holds every wait for the interpreter lock, a semaphore, a socket or
the device), a ``jax.profiler.TraceAnnotation`` named
``beacon.<stage>`` for ``work`` stages (always; free while nobody
captures a profile, and on the device trace's clock when somebody
does), and a :class:`Span` in the tree while tracing is enabled.
``/debug/status`` serves :meth:`Tracer.stage_summary`; a reader takes
the difference of two snapshots. Beside the stages, read only when a
snapshot is served: :class:`ThreadClock`, the CPU and context switches
of the process's Python threads by role (:data:`THREAD_ROLES`), and
:class:`LockTurnProbe`, which measures how long a thread that gave the
interpreter lock up waits to run again (stage ``runtime.lock_turn``).
"""

from __future__ import annotations

import collections
import functools
import gc
import os
import resource
import threading
import time
import timeit
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field

from ..telemetry import (
    _ambient,  # read in every scope: the attribute, not a call
    current_context,
    new_span_id,
    percentiles,
)

#: every stage of the serving path, name -> kind. ``work``: the thread
#: computes, or drives or blocks on the device (annotated on the
#: profiler's clock). ``wait``: the thread is parked behind a gate, a
#: queue or another thread, or the interval is a hand-off between two
#: threads (never annotated: sixteen parked request threads would
#: out-cover the one working thread in every idle gap of a device
#: trace). ``total``: an interval that encloses other stages, kept for
#: its readers and left out of every sum.
STAGES = {
    # HTTP + router
    "http.read": "work",  # request line read -> body parsed
    "api.total": "total",  # app.handle entry -> return (meta.elapsedTimeMs)
    "api.admit": "wait",  # shaping.admit + admission.admit entry
    "api.parse": "work",  # admitted -> request parsed and dispatched
    "api.envelope": "work",  # aggregation + the response envelope
    "api.finish": "work",  # after _handle: SLO, accounting, plans, meta
    "http.write": "work",  # json.dumps, headers, wfile.write
    # filter resolution
    "filters.resolve": "work",  # datasets of the assembly, filters -> samples
    # one term's closure read from the ontology store, inside
    # filters.resolve (readers of both count the outer one only)
    "filters.descendants": "work",
    # admission, runner
    "runner.lookup": "work",  # hash, the lookup in memory and the claim
    "runner.wait": "wait",  # submit -> execution start on the pool
    # waiting for the job table's one lock (the writer's transaction, a
    # restored job's read; readers subtract it from the work stages
    # around it); timed only when the lock is contended
    "runner.table_wait": "wait",
    # the writer thread's transaction: one sample a commit, serving the
    # finished jobs it stores
    "runner.persist": "work",
    # response cache
    "cache.lookup": "work",
    # engine
    "engine.plan": "work",  # targets, path choice, selected-sample masks
    # a filtered request's samples resolved against one shard: names ->
    # positions -> mask words, or the shard's kept answer; inside
    # engine.plan (the mesh stack's responses: engine.materialize), so
    # readers of both count the outer one only, as filters.descendants
    "engine.select": "work",
    "engine.fanout": "wait",  # parked while the scatter pool serves targets
    # one target's wait inside engine.fanout: submitted to the scatter
    # pool -> a pool thread starts it (beside the chain: the request's
    # thread is in engine.fanout meanwhile)
    "engine.pool_wait": "wait",
    "engine.materialize": "work",  # one target's response
    # micro-batcher
    # submit -> the launcher has the batch, less the entry's share of
    # batcher.pipeline; one sample a request
    "batcher.wait": "wait",
    # the leader's wait for a fetch-pipeline slot, before it pops; one
    # sample a launch, serving each entry for the part it queued through
    "batcher.pipeline": "wait",
    "batcher.fetch_wait": "wait",  # dispatch returned -> fetcher starts
    "handoff.back": "wait",  # result set -> the waiting thread runs again
    # query encode, H2D and D2H, inside ops/
    "kernel.encode": "work",  # encode_queries, pack_queries / pack_q8, padding
    # the upload and the jitted call until it returns: ONE packed array a
    # launch of the fused and mesh families, two or three of the scatter's
    "kernel.dispatch": "work",
    "kernel.readback": "work",  # device_get until host arrays exist
    "kernel.unpack": "work",  # host arrays -> each query's rows and counts
    # runtime
    "gc": "work",  # one collection, annotated beacon.gc.gen<n>
    # how late LockTurnProbe's thread ran again after a 50 ms wait: the
    # timer's slack plus its turn at the interpreter lock (beside the
    # chain, twenty samples a second a running app)
    "runtime.lock_turn": "wait",
    # the batcher's composite intervals behind /debug/status's old keys
    # (queue_wait_ms, encode_ms, launch_ms, fetch_ms, exec_ms): same
    # clock reads as the stages above, same measuring points as before
    "batcher.queue_wait": "total",
    "batcher.encode": "total",
    "batcher.launch": "total",
    "batcher.fetch": "total",
    "batcher.exec": "total",
}

#: the stages a request passes between ``app.handle``'s entry and its
#: return, in order and without overlap: their ``req_ms`` over
#: ``api.total``'s ``sum_ms`` is the span coverage. ``http.read``,
#: ``api.finish`` and ``http.write`` lie outside on the socket's side
#: (``api.total`` is what ``meta.elapsedTimeMs`` reports); ``gc`` and
#: ``runner.persist`` run beside the chain, not in it.
CHAIN = (
    "api.admit", "api.parse", "filters.resolve", "runner.lookup",
    "runner.wait", "cache.lookup", "engine.plan", "batcher.wait",
    "batcher.pipeline", "kernel.encode", "kernel.dispatch",
    "batcher.fetch_wait", "kernel.readback", "kernel.unpack",
    "handoff.back", "engine.fanout", "engine.materialize", "api.envelope",
)

#: ``elapsed_ms`` less the chain's sum, as a label beside the chain's
#: stages: the time between stages
UNNAMED = "unnamed"
#: label values of ``request.tail_ms{stage}`` / ``request.body_ms{stage}``
FOLD_LABELS = (*CHAIN, UNNAMED)
#: finishes a route's thresholds are taken over
CLASS_RING = 2048
#: ... and the fewest they are taken from: a twentieth of them is one
CLASS_AFTER = 20

#: thread-name prefix -> role of ``runtime.thread_cpu_ms{role}`` and its
#: siblings (:class:`ThreadClock`); a thread no prefix names is ``other``
#: (the canary's, the compactor's and the probe's own among them)
THREAD_ROLES = {
    "request": "request",  # the HTTP server's handler threads
    "query-runner": "query-runner",
    "engine-scatter": "engine-scatter",
    "kernel-launch": "kernel-launch",
    "kernel-fetch": "kernel-fetch",
    "batch-drain": "batch-drain",
    "query-jobs-writer": "query-jobs-writer",
    "canary-prober": "canary-prober",
    "MainThread": "main",
}
ROLES = (*THREAD_ROLES.values(), "other")

#: samples kept per stage for p50/p95/p99
STAGE_RING = 16384
#: samples a stage holds unfolded before a writer folds them itself
FOLD_AT = 1024

# a scope's clocks, bound once
_wall = time.perf_counter
_cpu_ns = time.thread_time_ns


def cpu_clock_stride() -> int:
    """Scopes of a stage to ONE that reads its thread's CPU clock: 1
    where the clock is a plain system call (0.3 us on Linux), six per
    microsecond of its cost where it is not, so that the two reads add
    a third of a microsecond to the mean scope whatever they cost.
    Under a sandboxed kernel (gVisor, which the chip's machines run)
    every system call is 6 us and more under load, read from every
    scope that took a tenth of ``kg1.unique``'s rate (PERF.md 6, PR
    36), and the clock there moves in 10 ms ticks, so a reading is a
    sample either way. Odd, so that a stage a request passes twice
    is read at both places. Measured once a tracer: the best of five
    rounds."""
    cost_us = min(
        timeit.timeit(_cpu_ns, number=8) for _ in range(5)
    ) / 8 * 1e6
    return 1 if cost_us < 1.0 else round(6 * cost_us) | 1


# jax.profiler, resolved at the first ``work`` stage (False where JAX
# cannot be imported); TraceAnnotation is looked up per call
_profiler = None


def _find_profiler():
    try:
        import jax.profiler as found
    except Exception:
        found = False
    return found


def _annotation(label: str):
    """An annotation for the profiler's trace, or None while nobody
    captures one (or JAX is absent)."""
    global _profiler
    if _profiler is None:
        _profiler = _find_profiler()
    if _profiler and _profiler.TraceAnnotation.is_enabled():
        return _profiler.TraceAnnotation(label)
    return None


class StageStats:
    """One stage: its always-on sinks, and the scope that feeds them.

    The sinks: ``count`` samples, their ``sum_ms``, ``req_ms`` (the sum
    of duration x requests served by the sample, so that coverage still
    adds up once a launch serves several), ``cpu_ms`` (what the scopes
    cost their threads' processors, by ``time.thread_time_ns``: a
    thread parked on the interpreter lock, a semaphore, a socket or the
    device accrues none; 0 for a sample that was observed, which has no
    thread) and the bounded ring behind the quantiles.

    ``with stage(name):`` enters this very object: the open reading is
    kept by thread, so a stage must not nest inside itself on one
    thread, and a sample costs its writer two dictionary operations and
    one ``deque.append`` of a float for each of its two clocks, whether
    it served one request or (on a fan-out's pool thread) none. Nothing
    the collector tracks is allocated and no lock is taken: sixteen
    request threads pass some twenty stages each, a fan-out thirty-two
    more on its pool, and both showed on the chip (PERF.md 6, PR 24).
    The CPU clock is read inside the wall clock's two reads, and a
    sample's wall is written before its CPU and folded after it, so
    ``cpu_ms`` never passes ``sum_ms`` where every scope reads it.
    Where the clock is dear only every ``_every``-th scope of the stage
    does (:func:`cpu_clock_stride`) and counts ``_every`` times: an
    estimate that is right over many scopes, not scope by scope.
    Readers, and a writer that finds ``FOLD_AT`` samples waiting, fold
    them into the sums under the lock, so the sums are monotone and
    exact whenever they are read."""

    __slots__ = ("name", "kind", "label", "tracer", "count", "sum_ms",
                 "req_ms", "cpu_ms", "_ring", "_pending", "_beside", "_cpu",
                 "_lock", "_open", "_open_cpu", "_live", "_last", "_every",
                 "_due")

    def __init__(self, name: str, kind: str, tracer: "Tracer"):
        self.name = name
        self.kind = kind
        self.label = f"beacon.{name}" if kind == "work" else None
        self.tracer = tracer
        self.count = 0
        self.sum_ms = 0.0
        self.req_ms = 0.0
        self.cpu_ms = 0.0
        self._ring: collections.deque = collections.deque(maxlen=STAGE_RING)
        # durations of samples that served one request, not yet folded
        self._pending: collections.deque = collections.deque()
        # ... and of samples that served none (a fan-out's pool threads)
        self._beside: collections.deque = collections.deque()
        # ... and the CPU of either kind's scopes, each after its wall
        self._cpu: collections.deque = collections.deque()
        # re-entrant: a collection can start inside a reader of the
        # ``gc`` stage, on the reader's own thread
        self._lock = threading.RLock()
        # by thread: the open scope's start on both clocks, its
        # annotation and span (only while a profile is captured or the
        # span tree is on), and the last duration (``ms``)
        self._open: dict[int, float] = {}
        self._open_cpu: dict[int, int] = {}
        # scopes to one that reads the CPU clock, and how many are left
        # until the next (racing threads only shift the phase)
        self._every = self._due = tracer.cpu_every
        self._live: dict[int, tuple] = {}
        self._last: dict[int, float] = {}

    # -- the scope ------------------------------------------------------------

    def __enter__(self):
        me = threading.get_ident()
        ann = _annotation(self.label) if self.label is not None else None
        tracer = self.tracer
        tree = (tracer._enabled or tracer._overrides) and tracer.is_enabled
        if ann is not None:
            ann.__enter__()
        self._open[me] = t0 = _wall()
        due = self._due - 1
        if due > 0:
            self._due = due
        else:
            self._due = self._every
            self._open_cpu[me] = _cpu_ns()
        if tree or ann is not None:
            span = tracer._open(self.name, t0, {}) if tree else None
            self._live[me] = (ann, span)
        return self

    def __exit__(self, *exc):
        me = threading.get_ident()
        cpu = None
        if self._open_cpu:  # empty between the scopes of a stride
            cpu = self._open_cpu.pop(me, None)
            if cpu is not None:
                cpu = _cpu_ns() - cpu
        t1 = _wall()
        t0 = self._open.pop(me, None)
        if t0 is None:  # closed already
            return False
        self._last[me] = ms = (t1 - t0) * 1e3
        if self._live:
            ann, span = self._live.pop(me, (None, None))
            if ann is not None:
                ann.__exit__(None, None, None)
            if span is not None:
                self.tracer._finish(span, t1)
        serving = self.tracer._serving.get(me)
        if serving is None:
            # the thread's own request, if it has one: its vector takes
            # what the stage's req_ms takes
            self.add(ms)
            ctx = _ambient.ctx
            if ctx is not None:
                ctx.stages[self.name] += ms
        else:
            self.add(ms, serving.n)
            serving.credit(self.name, ms)
        if cpu:
            self.add_cpu(cpu * 1e-6 * self._every)
        return False

    def close(self) -> None:
        """End the stage before its ``with`` block does (a stage that
        covers only the entry of the blocks nested in it); closing
        twice is harmless."""
        self.__exit__(None, None, None)

    @property
    def ms(self) -> float:
        """The duration of the scope this thread closed last."""
        return self._last.get(threading.get_ident(), 0.0)

    def note(self, **kw) -> None:
        """Metadata for the scope's span, where the tree is on."""
        span = self._live.get(threading.get_ident(), (None, None))[1]
        if span is not None:
            span.meta.update(kw)

    # -- the sinks ------------------------------------------------------------

    def add(self, ms: float, n: float = 1) -> None:
        if n == 1 or n == 0:
            pending = self._pending if n else self._beside
            pending.append(ms)
            if len(pending) > FOLD_AT:
                self._fold()
            return
        with self._lock:
            self.count += 1
            self.sum_ms += ms
            self.req_ms += ms * n
            self._ring.append(ms)

    def add_cpu(self, cpu_ms: float) -> None:
        """The CPU of a scope whose wall :meth:`add` has just taken."""
        self._cpu.append(cpu_ms)

    @staticmethod
    def _drain(pending: collections.deque, ring: collections.deque):
        """Move what waits in ``pending`` to the ring; (count, sum)."""
        count, sum_ms = 0, 0.0
        try:
            while True:
                ms = pending.popleft()
                count += 1
                sum_ms += ms
                ring.append(ms)
        except IndexError:
            pass
        return count, sum_ms

    def _fold(self) -> None:
        with self._lock:
            # CPU first: a writer appends its wall before its CPU, so
            # every sample whose CPU is summed here has its wall summed
            # below, and cpu_ms never passes sum_ms
            cpu = self._cpu
            cpu_ms = sum(cpu.popleft() for _ in range(len(cpu)))
            served, served_ms = self._drain(self._pending, self._ring)
            beside, beside_ms = self._drain(self._beside, self._ring)
            self.count += served + beside
            self.sum_ms += served_ms + beside_ms
            self.req_ms += served_ms
            self.cpu_ms += cpu_ms

    def _samples(self) -> list:
        for _ in range(3):
            try:
                return list(self._ring)
            except RuntimeError:  # a collection's fold landed mid-copy
                continue
        return []

    def counts(self) -> tuple:
        with self._lock:
            self._fold()
            return self.count, self.sum_ms, self.req_ms

    def quantiles(self) -> dict:
        with self._lock:
            self._fold()
            xs = self._samples()
        return percentiles(xs)

    def summary(self) -> dict:
        with self._lock:
            count, sum_ms, req_ms = self.counts()
            cpu_ms = self.cpu_ms
            xs = self._samples()
        return {
            "count": count,
            "sum_ms": round(sum_ms, 3),
            "req_ms": round(req_ms, 3),
            "cpu_ms": round(cpu_ms, 3),
            **percentiles(xs),
        }

    def reset(self) -> None:
        with self._lock:
            self._pending.clear()
            self._beside.clear()
            self._cpu.clear()
            self.count = 0
            self.sum_ms = self.req_ms = self.cpu_ms = 0.0
            self._ring.clear()


@dataclass(eq=False)  # identity equality: `in`-checks on the span stack
class Span:
    """One finished timed region. ``children`` preserves call structure.

    ``trace_id`` ties the span to the distributed request identity the
    telemetry plane carries (telemetry.RequestContext): every span
    opened while a request context is ambient — including on a worker
    host that received the id via the ``X-Beacon-Trace`` header —
    shares that request's trace id, so one fan-out query's spans
    correlate across processes. ``span_id`` names this span itself.
    """

    name: str
    t_start: float
    t_end: float = 0.0
    meta: dict = field(default_factory=dict)
    children: list["Span"] = field(default_factory=list)
    trace_id: str = ""
    span_id: str = ""

    @property
    def elapsed(self) -> float:
        return self.t_end - self.t_start

    def flatten(self):
        yield self
        for c in self.children:
            yield from c.flatten()

    def to_dict(self) -> dict:
        """JSON-ready form for the /_trace debug endpoint."""
        return {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "elapsedMs": round(1e3 * self.elapsed, 3),
            "meta": dict(self.meta),
            "children": [c.to_dict() for c in self.children],
        }


class _NullSpan:
    """No-op context manager handed out while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **kw):
        pass


_NULL = _NullSpan()


class _Serving:
    """``with tracer.serving(n):`` — see :meth:`Tracer.serving`. Holds
    no state of one use, so the tracer keeps one per bare ``n`` and a
    pool task allocates nothing for its scope (a launch that names its
    entries' contexts allocates this one object); scopes do not nest on
    a thread (launcher, fetcher and fan-out pools are threads of their
    own)."""

    __slots__ = ("_by_thread", "n", "ctxs")

    def __init__(self, by_thread: dict, n, ctxs=None):
        self._by_thread = by_thread
        self.n = n
        self.ctxs = ctxs

    def __enter__(self):
        self._by_thread[threading.get_ident()] = self

    def __exit__(self, *exc):
        self._by_thread.pop(threading.get_ident(), None)
        return False

    def credit(self, name: str, ms: float) -> None:
        """A sample closed inside the scope, to the vectors of the
        requests it served: each of a launch's entries, or, serving
        none, ``beside`` of the request the pool thread works for. A
        bare count over 0 names nobody (the writer's transaction)."""
        ctxs = self.ctxs
        if ctxs is not None:
            for ctx in ctxs:
                ctx.stages[name] += ms
        elif not self.n:
            ctx = _ambient.ctx
            if ctx is not None:
                ctx.beside[name] += ms


class _Registry(dict):
    """name -> StageStats; an unregistered name raises at the call."""

    def __missing__(self, name):
        raise ValueError(
            f"unregistered stage {name!r}: add it to utils.trace.STAGES"
        )


class _ActiveSpan:
    __slots__ = ("tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer = tracer
        self.span = span

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer._finish(self.span)
        return False

    def note(self, **kw):
        """Attach metadata (bytes scanned, batch size, ...) to the span."""
        self.span.meta.update(kw)


class Tracer:
    def __init__(self, enabled: bool | None = None, keep_trees: int = 32):
        if enabled is None:
            enabled = os.environ.get("SBEACON_TRACE", "") not in ("", "0")
        self._enabled = enabled
        self._keep_trees = keep_trees
        self._local = threading.local()
        self._lock = threading.Lock()
        # name -> [count, total, min, max]
        self.stats: dict[str, list[float]] = {}
        self.trees: list[Span] = []
        # trace_id -> [root Span, ...] over the SAME retained trees:
        # /_trace?trace_id= resolves in O(trees-for-id) instead of
        # re-serialising and filtering the whole ring per lookup
        # (exemplar-to-trace resolution is a per-dashboard-click path)
        self._by_trace: dict[str, list[Span]] = {}
        #: scopes of a stage to one that reads the thread's CPU clock
        self.cpu_every = cpu_clock_stride()
        self._stages = _Registry(
            (n, StageStats(n, k, self)) for n, k in STAGES.items()
        )
        # threads inside an ``enabled()`` scope: with none, and the
        # process-wide flag off, a stage skips the span tree on two
        # attribute reads
        self._overrides = 0
        # thread id -> the serving scope that thread is inside: a launch
        # of the batcher, a pool task of a fan-out
        self._serving: dict[int, _Serving] = {}
        self._serving_scopes: dict[int, _Serving] = {}

    # -- stages: always on ----------------------------------------------------

    def stage(self, name: str) -> StageStats:
        """``with tracer.stage(name):`` times one boundary of the
        serving path. The sample serves the thread's ambient
        :meth:`serving` count of requests, or 1."""
        return self._stages[name]

    def observe(self, name: str, ms: float, n: float = 1, ctxs=()) -> None:
        """Feed an interval whose two ends were read elsewhere (a
        hand-off between threads, a composite): it has no thread, so
        its ``cpu_ms`` stays 0. ``work`` stages are scoped, never
        observed: the annotation needs the live scope. ``ctxs``: the
        contexts of the requests the interval belongs to (None among
        them skipped), each credited ``ms`` in its vector, or in
        ``beside`` where the sample serves none."""
        acc = self._stages[name]
        if acc.label is not None:
            raise ValueError(f"work stage {name!r} must be a `with stage(...)`")
        acc.add(ms, n)
        for ctx in ctxs:
            if ctx is not None:
                (ctx.stages if n else ctx.beside)[name] += ms

    def serving(self, n: int, ctxs=None) -> _Serving:
        """Stages opened on this thread inside the scope serve ``n``
        requests each (a launch of the micro-batcher; 0 on a pool thread
        whose request is parked in ``engine.fanout`` meanwhile).
        ``ctxs``: the contexts of the ``n``, whose vectors each sample
        is credited to; without them a scope that serves none credits
        ``beside`` of the thread's ambient context."""
        if ctxs is not None:
            return _Serving(self._serving, n, ctxs)
        scope = self._serving_scopes.get(n)
        if scope is None:
            scope = self._serving_scopes[n] = _Serving(self._serving, n)
        return scope

    def stage_quantiles(self, name: str) -> dict:
        """{p50, p95, p99} of one stage's ring, or {} before a sample."""
        return self._stages[name].quantiles()

    def stage_counts(self, name: str) -> tuple:
        """(count, sum_ms, req_ms) of one stage: monotone, never reset."""
        return self._stages[name].counts()

    def stage_summary(self) -> dict:
        """{stage: {count, sum_ms, req_ms, cpu_ms, p50, p95, p99}} for every
        registered stage (no quantiles before its first sample)."""
        return {n: acc.summary() for n, acc in self._stages.items()}

    def reset_stages(self) -> None:
        for acc in self._stages.values():
            acc.reset()

    # -- gating -------------------------------------------------------------

    @property
    def is_enabled(self) -> bool:
        override = getattr(self._local, "override", None)
        return self._enabled if override is None else override

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    @contextmanager
    def enabled(self, on: bool = True):
        """Thread-scoped override: ``with tracer.enabled(): ...``. The
        override lives in thread-local state so concurrent scopes in other
        threads neither see it nor clobber the process-wide flag."""
        prev = getattr(self._local, "override", None)
        self._local.override = on
        with self._lock:
            self._overrides += 1
        try:
            yield self
        finally:
            self._local.override = prev
            with self._lock:
                self._overrides -= 1

    # -- span recording -----------------------------------------------------

    def span(self, name: str, **meta):
        if not self.is_enabled:
            return _NULL
        return _ActiveSpan(self, self._open(name, time.perf_counter(), meta))

    def _open(self, name: str, t_start: float, meta: dict) -> Span:
        sp = Span(name=name, t_start=t_start, meta=dict(meta))
        ctx = current_context()
        if ctx is not None:
            sp.trace_id = ctx.trace_id
        sp.span_id = new_span_id()
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append(sp)
        return sp

    def _finish(self, sp: Span, t_end: float | None = None) -> None:
        sp.t_end = time.perf_counter() if t_end is None else t_end
        # a span entered on one thread may be exited on another (the
        # batcher's launcher/fetcher pools hand work across threads):
        # the finishing thread then has no span stack at all — record
        # stats only instead of raising AttributeError mid-request
        stack = getattr(self._local, "stack", None) or ()
        was_root = False
        if sp in stack:
            # spans still open above sp were opened inside its scope: a
            # mis-ordered exit adopts them as children rather than
            # discarding them (or sp's own ancestors)
            while stack[-1] is not sp:
                sp.children.append(stack.pop())
            stack.pop()
            # spans beneath that already finished were exited on
            # ANOTHER thread (stats-only, never popped here): they can
            # never be popped by their own exit, so left in place they
            # would adopt every later tree on this thread and grow
            # unboundedly — drop them; their stats are already recorded
            while stack and stack[-1].t_end:
                stack.pop()
            if stack:
                stack[-1].children.append(sp)
            else:
                was_root = True
        # else: sp was already adopted by a mis-ordered ancestor exit —
        # record stats only, leave the stack alone
        with self._lock:
            st = self.stats.get(sp.name)
            el = sp.elapsed
            if st is None:
                self.stats[sp.name] = [1, el, el, el]
            else:
                st[0] += 1
                st[1] += el
                st[2] = min(st[2], el)
                st[3] = max(st[3], el)
            if was_root:  # a completed root tree
                self.trees.append(sp)
                if sp.trace_id:
                    self._by_trace.setdefault(sp.trace_id, []).append(sp)
                if len(self.trees) > self._keep_trees:
                    evicted = self.trees[: -self._keep_trees]
                    del self.trees[: -self._keep_trees]
                    for old in evicted:
                        bucket = self._by_trace.get(old.trace_id)
                        if bucket is None:
                            continue
                        try:
                            bucket.remove(old)
                        except ValueError:
                            pass
                        if not bucket:
                            del self._by_trace[old.trace_id]

    def wrap(self, name: str | None = None):
        """Decorator form: ``@tracer.wrap("kernel.run")``."""

        def deco(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def inner(*a, **kw):
                with self.span(label):
                    return fn(*a, **kw)

            return inner

        return deco

    # -- reporting ----------------------------------------------------------

    def reset(self) -> None:
        with self._lock:
            self.stats.clear()
            self.trees.clear()
            self._by_trace.clear()

    def recent_trees(self, trace_id: str | None = None) -> list[dict]:
        """The retained complete span trees as JSON-ready dicts (the
        /_trace payload), newest last; ``trace_id`` filters to one
        distributed request's spans via the maintained per-trace index
        — O(matching trees), not a serialise-and-scan of the whole
        ring (the exemplar-click resolution path)."""
        with self._lock:
            if trace_id is not None:
                trees = list(self._by_trace.get(trace_id, ()))
            else:
                trees = list(self.trees)
        return [t.to_dict() for t in trees]

    def report(self) -> str:
        """Aggregate table + the most recent span tree."""
        with self._lock:
            lines = [
                f"{'span':<40} {'count':>7} {'total_s':>10} "
                f"{'mean_ms':>9} {'min_ms':>9} {'max_ms':>9}"
            ]
            for name in sorted(self.stats):
                n, tot, mn, mx = self.stats[name]
                lines.append(
                    f"{name:<40} {int(n):>7} {tot:>10.4f} "
                    f"{1e3 * tot / n:>9.3f} {1e3 * mn:>9.3f} {1e3 * mx:>9.3f}"
                )
            if self.trees:
                lines.append("")
                lines.extend(self._render(self.trees[-1], 0))
        return "\n".join(lines)

    def _render(self, sp: Span, depth: int):
        meta = (
            " " + " ".join(f"{k}={v}" for k, v in sp.meta.items())
            if sp.meta
            else ""
        )
        yield f"{'  ' * depth}{sp.name}: {1e3 * sp.elapsed:.3f}ms{meta}"
        for c in sp.children:
            yield from self._render(c, depth + 1)


#: process-global tracer — modules do ``from ..utils.trace import tracer``
tracer = Tracer()

if tracer.is_enabled:
    # enabled-by-env processes print the aggregate report at exit, so
    # SBEACON_TRACE=1 always yields output even without the /_trace route
    import atexit
    import sys

    atexit.register(
        lambda: print(
            "\n== sbeacon trace report ==\n" + tracer.report(),
            file=sys.stderr,
        )
    )


def span(name: str, **meta):
    return tracer.span(name, **meta)


_process_stages = tracer._stages


def stage(name: str) -> StageStats:
    return _process_stages[name]


# -- the interpreter's collections, as a stage --------------------------------

#: collections per generation and their summed pause, for /metrics
gc_pauses = [0, 0, 0]
_GC_LABELS = ("beacon.gc.gen0", "beacon.gc.gen1", "beacon.gc.gen2")
_gc_open = None


def _gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: the collection is the ``gc`` stage, its
    annotation ``beacon.gc.gen<n>`` opened at start and closed at stop.
    Collections do not nest, so one module slot holds the open one."""
    global _gc_open
    if phase == "start":
        ann = _annotation(_GC_LABELS[info["generation"]])
        if ann is not None:
            ann.__enter__()
        _gc_open = (ann, time.perf_counter(), time.thread_time_ns())
    elif _gc_open is not None:
        ann, t0, c0 = _gc_open
        _gc_open = None
        cpu_ms = (time.thread_time_ns() - c0) * 1e-6
        ms = (time.perf_counter() - t0) * 1e3
        if ann is not None:
            ann.__exit__(None, None, None)
        acc = tracer._stages["gc"]
        acc.add(ms)
        acc.add_cpu(cpu_ms)
        gc_pauses[info["generation"]] += 1


def install_gc_stage() -> None:
    """Hook the collector once per process (the app does, at start)."""
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)


# -- the process's threads by role, and the interpreter lock's turn ------------


def thread_role(name: str) -> str:
    for prefix, role in THREAD_ROLES.items():
        if name.startswith(prefix):
            return role
    return "other"


_TICK_MS = 1e3 / os.sysconf("SC_CLK_TCK")


def _task_reading(native_id: int):
    """(CPU ms, voluntary, involuntary context switches) of one thread
    of this process as the kernel accounts them, or None once it is
    gone. A sandboxed kernel (gVisor) keeps no ``schedstat`` and counts
    no switches: the CPU is then ``stat``'s ticks and the switches 0."""
    task = f"/proc/self/task/{native_id}/"
    try:
        try:
            with open(task + "schedstat") as f:
                cpu_ms = int(f.read().split()[0]) * 1e-6
        except FileNotFoundError:
            with open(task + "stat") as f:
                fields = f.read().rpartition(")")[2].split()
            cpu_ms = (int(fields[11]) + int(fields[12])) * _TICK_MS
        with open(task + "status") as f:
            status = f.read()
        at = status.find("\nvoluntary_ctxt_switches:")
        yields, preempted = (
            int(line.split()[1]) for line in status[at:].split("\n")[1:3]
        ) if at >= 0 else (0, 0)
    except (OSError, ValueError, IndexError):
        return None
    return cpu_ms, yields, preempted


class ThreadClock:
    """CPU time and context switches of the process's Python threads,
    summed by role (:func:`thread_role`), read from ``/proc/self/task``
    when a snapshot is served and never on a request's path.

    A voluntary context switch (``yields``) is a thread giving its
    processor up: a hand-over of the interpreter lock that it then has
    to wait for, a parked wait, a blocking call; an involuntary one
    (``preempted``) is the kernel taking it away. Monotone across a
    thread's exit: its last reading stays in its role's sums. A thread
    that ends by design (a connection's handler, a transient drainer)
    calls :meth:`leave` last, so that reading is its final one; any
    other is counted up to the last scan that saw it.
    ``process_cpu_ms`` is the whole process, the runtime's own threads
    (PJRT, XLA, sqlite) included, read after the threads so that their
    sum cannot pass it. One scan serves every family of one rendering:
    a reading younger than :attr:`KEEP_S` is served again."""

    KEEP_S = 0.02

    def __init__(self):
        self._lock = threading.Lock()
        # thread -> (role, CPU ms, yields, preempted), as last read
        self._seen: dict[threading.Thread, tuple] = {}
        self._gone = {role: [0.0, 0, 0] for role in ROLES}
        self._doc: dict | None = None
        self._read_at = 0.0

    def read(self) -> dict:
        """{cpu_ms, yields, preempted: {role: sum}, process_cpu_ms}."""
        with self._lock:
            if self._doc is None or _wall() - self._read_at > self.KEEP_S:
                self._doc = self._scan()
                self._read_at = _wall()
            return self._doc

    def leave(self) -> None:
        """The calling thread is about to end: keep its final reading,
        which no scan could take later. One ``getrusage`` of the thread
        itself, the kernel's same account as the files' (a transient
        drainer passes here between two launches)."""
        thread = threading.current_thread()
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        with self._lock:
            self._seen[thread] = (
                thread_role(thread.name),
                (usage.ru_utime + usage.ru_stime) * 1e3,
                usage.ru_nvcsw, usage.ru_nivcsw,
            )

    def _scan(self) -> dict:
        def add(sums: dict, role: str, *reading) -> None:
            for i, v in enumerate(reading):
                sums[role][i] += v

        live = {}
        for thread in threading.enumerate():
            reading = thread.native_id and _task_reading(thread.native_id)
            if reading:
                live[thread] = (thread_role(thread.name), *reading)
        for thread, last in self._seen.items():
            now = live.get(thread)
            # gone, or renamed into another role since
            if now is None or now[0] != last[0]:
                add(self._gone, *last)
        self._seen = live
        sums = {role: list(v) for role, v in self._gone.items()}
        for reading in live.values():
            add(sums, *reading)
        return {
            "cpu_ms": {r: round(v[0], 3) for r, v in sums.items()},
            "yields": {r: v[1] for r, v in sums.items()},
            "preempted": {r: v[2] for r, v in sums.items()},
            "process_cpu_ms": round(time.process_time_ns() * 1e-6, 3),
        }


#: process-wide, as the threads are
thread_clock = ThreadClock()


class LockTurnProbe:
    """One daemon thread that measures the interpreter lock's turn:
    twenty times a second it waits :attr:`PERIOD_S` on an event and
    feeds how late it ran again to the stage ``runtime.lock_turn``. On
    an idle process that is the timer's slack (tens of microseconds);
    where the interpreter is always held it is what a thread that gave
    the lock up waits to have it back. Twenty acquisitions a second is
    its whole cost. It ends with ``close()``, or by itself once its
    owner is collected (an app that nobody closed)."""

    PERIOD_S = 0.05

    def __init__(self, owner, each_second=None):
        self._owner = weakref.ref(owner)
        # work of the owner's that wants a thread off the request's
        # path once a second (it must not hold the owner alive)
        self._each_second = each_second
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="lock-turn-probe", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        period, wait = self.PERIOD_S, self._stop.wait
        turn = tracer._stages["runtime.lock_turn"]
        each_second, per_second = self._each_second, round(1 / period)
        ticks = 0
        while self._owner() is not None:
            t0 = _wall()
            if wait(period):
                return
            # beside the chain: the sample serves no request
            turn.add(max(0.0, (_wall() - t0 - period) * 1e3), 0)
            ticks += 1
            if each_second is not None and ticks % per_second == 0:
                each_second()

    def close(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(1.0)


class _Side:
    """What the requests of one class (tail or body) of one route
    spent: their count and their milliseconds by ``FOLD_LABELS``."""

    __slots__ = ("count", "ms")

    def __init__(self):
        self.count = 0
        self.ms = [0.0] * len(FOLD_LABELS)

    def mean(self) -> dict:
        n = self.count or 1
        return {
            name: round(v / n, 3)
            for name, v in zip(FOLD_LABELS, self.ms) if v
        }


class _RouteFold:
    __slots__ = ("recent", "cuts", "cut_at", "total", "tail", "body",
                 "by_granularity", "tail_by_granularity")

    def __init__(self):
        # elapsed_ms of the route's latest finishes (append is atomic)
        self.recent: collections.deque = collections.deque(maxlen=CLASS_RING)
        # (p40, p60, p95) of ``recent``; nothing is classed before
        self.cuts = (float("inf"), float("-inf"), float("inf"))
        self.cut_at = 0  # ``total`` when the cuts were taken
        self.total = 0
        self.tail = _Side()
        self.body = _Side()
        self.by_granularity: dict = collections.defaultdict(int)
        self.tail_by_granularity: dict = collections.defaultdict(int)


class TailFold:
    """Where the slowest twentieth of a route's requests spent their
    time, beside its middle fifth (ISSUE 37): a stage's own p95 is over
    that stage's samples, not over the slow requests.

    :meth:`fold` takes a finished request's ``elapsed_ms`` (what
    ``api.total`` and ``meta.elapsedTimeMs`` report) and its stage
    vector and classes it against the route's RUNNING quantiles of
    ``elapsed_ms``: *tail* at or over the p95, *body* between the p40
    and the p60. A classed request (one in four) adds its chain stages
    and ``unnamed`` (``elapsed_ms`` less their sum: the time between
    stages) to its side's sums, so a side's labels add up to its
    requests' ``elapsed_ms``; every request counts in ``total`` and by
    granularity. One short lock a request. :meth:`refresh` takes the
    three cuts anew over the route's latest ``CLASS_RING`` finishes, off
    the request's path (the app's ``LockTurnProbe`` calls it once a
    second). The sums are monotone and served when ``/metrics`` is
    rendered (:meth:`series`); ``/debug/status`` serves :meth:`status`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._routes: dict[str, _RouteFold] = {}

    def fold(self, route: str, elapsed_ms: float, stages: dict,
             granularity: str) -> None:
        r = self._routes.get(route)
        if r is None:
            with self._lock:
                r = self._routes.setdefault(route, _RouteFold())
        r.recent.append(elapsed_ms)
        lo, hi, tail = r.cuts
        if elapsed_ms >= tail:
            side = r.tail
        elif lo <= elapsed_ms <= hi:
            side = r.body
        else:
            side = None
        if side is not None:
            get = stages.get
            ms = [get(name, 0.0) for name in CHAIN]
            ms.append(elapsed_ms - sum(ms))
        with self._lock:
            r.total += 1
            r.by_granularity[granularity] += 1
            if side is None:
                return
            if side is r.tail:
                r.tail_by_granularity[granularity] += 1
            side.count += 1
            side.ms = [a + b for a, b in zip(side.ms, ms)]

    def refresh(self) -> None:
        """The three cuts of every route that finished a request since
        they were taken."""
        for r in list(self._routes.values()):
            total = r.total
            if total == r.cut_at:
                continue
            try:
                xs = sorted(r.recent)
            except RuntimeError:  # an append landed mid-copy: next time
                continue
            n = len(xs)
            if n >= CLASS_AFTER:
                r.cuts = (xs[int(0.4 * n)], xs[int(0.6 * n)],
                          xs[int(0.95 * n)])
                r.cut_at = total

    def series(self) -> dict:
        """The sums over every route, for the ``request.*`` series."""
        out = {
            "tail_count": 0, "body_count": 0, "classed_total": 0,
            "tail_ms": dict.fromkeys(FOLD_LABELS, 0.0),
            "body_ms": dict.fromkeys(FOLD_LABELS, 0.0),
            "tail_by_granularity": collections.Counter(),
            "classed_by_granularity": collections.Counter(),
        }
        with self._lock:
            for r in self._routes.values():
                out["classed_total"] += r.total
                out["classed_by_granularity"].update(r.by_granularity)
                out["tail_by_granularity"].update(r.tail_by_granularity)
                for key, side in (("tail", r.tail), ("body", r.body)):
                    out[f"{key}_count"] += side.count
                    sums = out[f"{key}_ms"]
                    for name, v in zip(FOLD_LABELS, side.ms):
                        sums[name] += v
        return out

    def status(self) -> dict:
        """Per route: the cuts, the counts and each side's mean vector."""
        with self._lock:
            return {
                route: {
                    "thresholdsMs": dict(zip(
                        ("p40", "p60", "p95"),
                        (round(c, 3) for c in r.cuts),
                    )) if r.cut_at else None,
                    "classed": r.total,
                    "tail": r.tail.count,
                    "body": r.body.count,
                    "tailMeanMs": r.tail.mean(),
                    "bodyMeanMs": r.body.mean(),
                }
                for route, r in sorted(self._routes.items())
            }


def graft_launch_span(active, *, elapsed_ms: float = 0.0, **meta) -> None:
    """Adopt one device launch as a ``device.launch`` child span of an
    open span — the in-process twin of the coordinator's worker-span
    graft (parallel/dispatch.py ``_graft_worker_spans``): the launch
    already happened inside ``active``'s scope, so it lays out as the
    trailing ``elapsed_ms`` of it. No-op while tracing is disabled
    (``active`` is the null span) — the kernel hot path pays one
    getattr."""
    sp = getattr(active, "span", None)
    if sp is None:
        return
    now = time.perf_counter()
    sp.children.append(
        Span(
            name="device.launch",
            t_start=now - elapsed_ms / 1e3,
            t_end=now,
            meta=dict(meta),
            trace_id=sp.trace_id,
            span_id=new_span_id(),
        )
    )
