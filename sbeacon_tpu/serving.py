"""Request micro-batcher: concurrent queries share one kernel launch.

SURVEY.md §7 names this load-bearing: single ad-hoc REST queries are the
anti-pattern for a TPU (one query = one tiny vmap lane), so concurrent
requests must accumulate into one batched kernel invocation. The
reference faced the inverse economics — each query *fans out* to hundreds
of bcftools lambdas (reference: splitQuery/lambda_function.py:45-69) —
so this component has no reference counterpart; it is the TPU-native
replacement for that entire fan-out layer at serving time.

Leader-election design (no dedicated flusher thread, zero idle cost):
the first request into an empty accumulator becomes the leader, waits up
to ``max_wait_ms`` for followers (0 by default: nobody waits for
company), takes one of the accumulator's fetch-pipeline slots, and only
THEN pops: while every slot is held by a launch in flight the leader
stays claimed, so arrivals queue behind it as followers and one pop
takes what has gathered (backpressure before the pop is what batches).
The batch runs as one ``run_queries_auto`` call (scatter or XLA kernel
by index type) and each waiter is handed its row of the results.
Batch-shape bucketing lives inside the kernels (the active
kernel.TierLadder rungs — kernel.BATCH_TIERS is the legacy default —
plus the scatter chunk slots), so XLA compiles one program per tier
instead of one per batch size; a pop fills a launch only up to the
padded shape its first entry already pays for (``ops.launch_capacity``).

Ingest-while-serving contract: the accumulators here are keyed by the
DEVICE INDEX object (base shards, fused/mesh stacks), and delta shards
deliberately never reach this layer — they are small, host-matched
rows on the engine's per-target path, so a delta publish can neither
invalidate a warm accumulator nor trigger a tier recompile. Only a
compaction swaps a new base index in, at which point the usual lazy
rebuild (plus the compactor's inline ``rebuild_stacks``) re-warms the
programs off the request path.
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from dataclasses import dataclass

from .harness.faults import fault_point
from .ops import launch_capacity, run_queries_auto
from .ops.kernel import QueryResults, encode_queries
from .resilience import (
    NO_DEADLINE,
    BatchTimeout,
    Deadline,
    DeadlineExceeded,
    current_deadline,
)
from .plan import plan_stage
from .telemetry import (
    annotate,
    charge_cost_to,
    current_context,
    note_device_stage,
    request_context,
)
from .utils.trace import span, thread_clock, tracer


@dataclass(eq=False)  # an entry is only ever itself (acc.items.remove)
class _Pending:
    #: the submission's query specs — one for a plain submit, several
    #: for a fused multi-shard submission (submit_many); the result is
    #: the matching row-slice of the batched QueryResults
    specs: list
    event: threading.Event
    #: per-spec shard ids into a FusedDeviceIndex; None on single-shard
    #: indexes (all submissions in one accumulator share the index, so
    #: they either all carry ids or none do)
    shard_ids: list | None = None
    result: object = None
    error: BaseException | None = None
    t_submit: float = 0.0
    #: the part of this entry's queued time during which whoever led
    #: its launch was waiting for the fetch-pipeline slot (its share of
    #: ``batcher.pipeline``; the rest up to the launcher is
    #: ``batcher.wait``)
    slot_ms: float = 0.0
    #: the fetcher's clock reading when the results were in hand: the
    #: woken submitter's ``handoff.back`` starts there
    t_ready: float = 0.0
    #: combined bound (request deadline ∧ batch timeout) — when waits end
    deadline: Deadline = NO_DEADLINE
    #: request deadline alone — decides 504 (request's fault) vs 503
    #: (server-side wedge) when the combined bound expires
    req_deadline: Deadline = NO_DEADLINE
    #: priority lane (shaping.classify_lane, read from the ambient
    #: request context at submit): when the backlog exceeds one batch,
    #: interactive entries ride the next launch ahead of bulk ones
    lane: str = "interactive"
    #: the submitting request's context (cost attribution): the fetch
    #: stage pro-rates the launch's measured device time to each
    #: submission's share of the specs and charges it here — None
    #: (warmup, direct callers) charges the unattributed residue
    ctx: object = None


class _Accumulator:
    """Per-(device-index, caps) accumulation queue."""

    def __init__(self, pipeline_depth: int = 1):
        self.lock = threading.Lock()
        self.items: list[_Pending] = []
        self.leader_active = False
        # bounds launched-but-unfetched batches: whoever leads takes a
        # slot BEFORE it pops (holding the leadership while it waits),
        # the fetch stage gives it back. Depth 1 is fully serial
        # launch->fetch; depth 2 overlaps the host-side encode of
        # batch i+1 with the device execution of batch i. With every
        # slot taken, arrivals queue in ``items`` behind the waiting
        # leader and ride ONE launch when a slot frees: the
        # backpressure is what batches (continuous batching)
        self.pipeline = threading.BoundedSemaphore(max(1, pipeline_depth))


class _LaunchPool:
    """Minimal DAEMON-thread work pool for kernel launches.

    Not a ThreadPoolExecutor: concurrent.futures registers an atexit
    hook that JOINS its (non-daemon) workers, so a truly wedged launch
    — the exact failure this layer exists to bound — would block
    interpreter shutdown forever. Daemon workers let the process exit;
    the per-task Event gives the leader its bounded wait. Workers are
    created lazily, one per submit up to ``max_workers``, then reused.
    """

    def __init__(self, max_workers: int, name: str):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._max = max_workers
        self._name = name
        self._lock = threading.Lock()
        self._n_threads = 0
        self._closed = False

    def submit(self, fn, *args) -> threading.Event:
        """Enqueue fn(*args); returns an Event set when it finishes.
        Raises after close(): a task enqueued with no workers left
        would otherwise never run and its Event never fire, turning a
        shutdown race into a phantom 'wedged device'."""
        done = threading.Event()
        with self._lock:
            if self._closed:
                raise RuntimeError("launch pool is closed")
            self._q.put((fn, args, done))
            if self._n_threads < self._max:
                self._n_threads += 1
                threading.Thread(
                    target=self._worker,
                    name=f"{self._name}_{self._n_threads}",
                    daemon=True,
                ).start()
        return done

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:  # close() poison pill
                return
            fn, args, done = item
            try:
                fn(*args)
            finally:
                done.set()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            n = self._n_threads
        for _ in range(n):
            self._q.put(None)

    def depth(self) -> dict:
        """{'threads': spawned workers, 'queued': tasks not yet picked
        up} — the /metrics launcher-pool depth."""
        with self._lock:
            return {"threads": self._n_threads, "queued": self._q.qsize()}


class MicroBatcher:
    """Batches kernel launches per device index.

    ``submit`` blocks until the caller's query has executed (alone after
    ``max_wait_ms`` of quiet, or sooner as part of a fuller batch) and
    returns that query's row of the :class:`QueryResults`.
    """

    #: a queued bulk entry older than this is no longer sorted behind
    #: newly-arrived interactive entries — lane precedence must not
    #: become starvation when the backlog stays above one batch
    BULK_SORT_STARVATION_MS = 500.0

    def __init__(
        self,
        *,
        max_batch: int = 512,
        max_wait_ms: float = 2.0,
        default_timeout_s: float | None = None,
        pipeline_depth: int = 2,
    ):
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        # upper bound on any submit's wait for its kernel launch: even
        # a caller with no propagated deadline cannot block forever
        # behind a wedged launch (the pre-resilience follower hang).
        # None = unbounded (explicit opt-out, e.g. micro tests).
        self.default_timeout_s = default_timeout_s
        # launched-but-unfetched batches allowed per accumulator (the
        # launch/fetch overlap window); 1 = fully serial (old behavior)
        self.pipeline_depth = pipeline_depth
        # occupancy accounting (the soak harness's evidence that
        # batching engages under concurrency): {batch_size: n_launches}
        self._stats_lock = threading.Lock()
        self._batch_hist: dict[int, int] = {}
        # flattened query-spec count per launch: differs from
        # _batch_hist when fused multi-shard submissions ride along
        # (one submission = k specs) — the /metrics fused-batch hist
        self._fused_hist: dict[int, int] = {}
        self._n_submits = 0
        self._n_specs = 0
        # per-request latency decomposition (soak-tail attribution,
        # VERDICT r3 #10): the stages of utils/trace.py hold it — the
        # chain stages (batcher.wait, batcher.pipeline, kernel.*,
        # batcher.fetch_wait, handoff.back) and, from the same clock
        # reads, the composites timing_summary() reports under its old
        # keys (batcher.queue_wait / encode / launch / fetch / exec).
        # The queue-wait decomposition histogram (batcher.stage_ms,
        # stage label) takes the same readings once an app registry
        # wired it (register_metrics). None until then, so engines
        # without an app pay one attribute read
        self._stage_hist = None
        # resilience observability: submits that expired before their
        # launch (leader-side filter) / timed out waiting (follower)
        self._n_expired = 0
        self._n_timeouts = 0
        # weak-keyed by the DeviceIndex so accumulators die with their
        # index (re-ingestion replaces DeviceIndex objects; an id()-keyed
        # dict would leak one accumulator per replaced index and could
        # alias a recycled id onto stale state)
        self._accums: "weakref.WeakKeyDictionary[object, dict]" = (
            weakref.WeakKeyDictionary()
        )
        self._lock = threading.Lock()
        # launches run on this pool, NOT on the leader's own thread, so
        # the leader's wait for its batch is deadline-bounded like a
        # follower's: a wedged device strands a (daemon) launcher
        # thread — which recovers if the launch ever returns and never
        # blocks process exit — not the request thread and its
        # admission slot. A task here already holds its accumulator's
        # fetch-pipeline slot (the leader took it before the pop, on
        # its own thread and its own bound), so no launcher thread
        # parks on the semaphore; the leader blocks on the launch
        # stage until the dispatch returns.
        self._launcher = _LaunchPool(16, "kernel-launch")
        # device-to-host fetches run here, decoupled from launches:
        # while batch i's results stream back, the launcher is already
        # encoding + dispatching batch i+1
        self._fetcher = _LaunchPool(16, "kernel-fetch")

    def _accum(self, dindex, caps: tuple) -> _Accumulator:
        with self._lock:
            by_caps = self._accums.get(dindex)
            if by_caps is None:
                by_caps = {}
                self._accums[dindex] = by_caps
            acc = by_caps.get(caps)
            if acc is None:
                acc = by_caps[caps] = _Accumulator(self.pipeline_depth)
            return acc

    def submit(
        self,
        dindex,
        spec,
        *,
        window_cap: int,
        record_cap: int,
        timeout_s: float | None = None,
        shard_id: int | None = None,
    ):
        """Returns (exists, call_count, n_variants, all_alleles_count,
        n_matched, overflow, rows) for this one query — one row of the
        batched QueryResults. ``shard_id`` targets the query at one
        shard segment of a FusedDeviceIndex.

        The wait is bounded by the tightest of ``timeout_s``, the
        batcher's ``default_timeout_s``, and the caller thread's ambient
        request deadline: expiry raises :class:`BatchTimeout` (still
        queued — no launch happened in time) or
        :class:`DeadlineExceeded` (the leader filtered this entry as
        already-expired before launching)."""
        return self.submit_many(
            dindex,
            [spec],
            window_cap=window_cap,
            record_cap=record_cap,
            timeout_s=timeout_s,
            shard_ids=None if shard_id is None else [shard_id],
        )

    def submit_many(
        self,
        dindex,
        specs: list,
        *,
        window_cap: int,
        record_cap: int,
        timeout_s: float | None = None,
        shard_ids: list | None = None,
    ):
        """One fused submission of several specs (a k-dataset query
        against a FusedDeviceIndex): ALL of them ride in the same
        batch and therefore the same kernel launch, and the returned
        QueryResults carries one row per spec in order. Waiting/expiry
        semantics are exactly :meth:`submit`'s — the submission is one
        queue entry."""
        acc = self._accum(dindex, (window_cap, record_cap))
        req_deadline = current_deadline()
        deadline = req_deadline.combine(
            timeout_s if timeout_s is not None else self.default_timeout_s
        )
        ctx = current_context()
        lane = (ctx.notes.get("lane") if ctx is not None else None) or (
            "interactive"
        )
        me = _Pending(
            specs=list(specs),
            shard_ids=None if shard_ids is None else list(shard_ids),
            event=threading.Event(),
            t_submit=time.perf_counter(),
            deadline=deadline,
            req_deadline=req_deadline,
            lane=lane,
            ctx=ctx,
        )
        with self._stats_lock:
            self._n_submits += 1
            self._n_specs += len(me.specs)

        with acc.lock:
            acc.items.append(me)
            if acc.leader_active:
                lead = False
            else:
                acc.leader_active = True
                lead = True

        if lead:
            self._lead(acc, dindex, window_cap, record_cap, me, req_deadline)
            # the launch stage is done (or our entry was filtered) but
            # with the async fetch split the RESULT may still be in
            # flight — wait for it, bounded exactly like a follower
            me.event.wait(deadline.remaining())
            if not me.event.is_set():
                raise self._timeout_error(req_deadline)
        else:
            me.event.wait(deadline.remaining())
            if not me.event.is_set():
                # still queued: withdraw so an eventual launch doesn't
                # execute a query nobody is waiting for. Already
                # dequeued into an in-flight batch: the result (or
                # error) is coming but past this caller's bound — give
                # up anyway; the leader's later event.set() lands on a
                # _Pending nobody reads.
                with acc.lock:
                    try:
                        acc.items.remove(me)
                    except ValueError:
                        pass
                    timed_out = not me.event.is_set()
                if timed_out:
                    raise self._timeout_error(req_deadline)
        if me.error is not None:
            raise me.error
        # this thread runs again: the hand-off back from the fetcher
        # ends here (one reading, also the slow-query note's)
        t_wake = time.perf_counter()
        if me.t_ready:
            tracer.observe(
                "handoff.back", (t_wake - me.t_ready) * 1e3, ctxs=(me.ctx,)
            )
        # per-request stage note for the slow-query log: submit ->
        # result delivery (queue wait + device execute + fetch), the
        # batcher's share of this request's latency — plus the kernel
        # family that served it (DeviceIndex / FusedDeviceIndex /
        # ScatterDeviceIndex), so a tail is attributable to a dispatch
        # tier without cross-referencing counters
        batch_ms = round((t_wake - me.t_submit) * 1e3, 2)
        annotate(batch_ms=batch_ms, batch_index=type(dindex).__name__)
        plan_stage(
            "batch", decision=type(dindex).__name__, batch_ms=batch_ms
        )
        return me.result

    def _lead(
        self,
        acc: _Accumulator,
        dindex,
        window_cap,
        record_cap,
        me: _Pending,
        req_deadline=NO_DEADLINE,
    ):
        # Runs under a broad except: if the leader dies with anything
        # _execute doesn't swallow (e.g. KeyboardInterrupt in the
        # follower-wait window), leadership must not stay claimed —
        # queued followers would wait out their full timeouts, and with
        # no timeout configured, forever.
        try:
            # wait for followers: batch fills or the window lapses
            sleeper = threading.Event()  # timed wait without busy-looping
            waited = 0.0
            step = self.max_wait_s / 4 if self.max_wait_s > 0 else 0
            while waited < self.max_wait_s:
                with acc.lock:
                    if len(acc.items) >= self.max_batch:
                        break
                sleeper.wait(step)
                waited += step
            self._serve(acc, dindex, window_cap, record_cap, me, req_deadline)
        except (BatchTimeout, DeadlineExceeded):
            raise  # leader's own bound: batch/orphans stay live
        except BaseException as e:
            self._fail_queued(acc, e)
            raise

    def _fail_queued(self, acc: _Accumulator, e: BaseException) -> None:
        """Release leadership and fail everything still queued — the
        hard-death cleanup for a serving loop that cannot continue."""
        with acc.lock:
            acc.leader_active = False
            orphans, acc.items = acc.items, []
        for p in orphans:
            if not p.event.is_set():
                p.error = e
                p.event.set()

    def _serve(
        self, acc, dindex, window_cap, record_cap, me, req_deadline
    ) -> None:
        """The leadership loop: take a fetch-pipeline slot, pop what has
        gathered, filter expired entries, launch, wait bounded. The slot
        comes FIRST and leadership is held while waiting for it: with no
        slot free, arrivals append to ``acc.items`` as followers, and
        when one frees ONE pop takes them all (``_pop``). Nobody waits
        for company: a lone request that finds a slot free is popped and
        launched at once. ``me`` is the leading request's own entry
        (None when run as a background drainer): the moment its launch
        is dispatched, any remaining backlog is handed to a transient
        daemon drainer and this request RETURNS — a leader must not
        keep serving other requests' batches on its own clock (and its
        own admission slot). The drainer exists only while backlog
        does, so the zero-idle-cost property of leader election is
        kept."""
        while True:
            if me is not None and me.event.is_set():
                # our answer is ready: hand off any backlog and return.
                # Leadership transfer is atomic — the drainer starts
                # with leader_active still True, so no window exists in
                # which a new submit would elect a second leader.
                self._handoff_or_release(acc, dindex, window_cap, record_cap)
                return
            # the wait for the slot is bounded like every other wait
            # here: by the leading request's own deadline, a drainer's
            # by the default bound — a wedged fetch strands neither a
            # request thread nor the leadership
            t_slot = time.perf_counter()
            if not acc.pipeline.acquire(timeout=self._bound(me)):
                if me is not None:
                    # no launch in our time: withdraw as a follower
                    # would, pass the leadership on, and report the
                    # same 503 / 504
                    with acc.lock:
                        try:
                            acc.items.remove(me)
                        except ValueError:
                            pass
                    self._handoff_or_release(
                        acc, dindex, window_cap, record_cap
                    )
                    raise self._timeout_error(req_deadline)
                # a drainer goes on waiting while anyone is queued
                # (each entry leaves at its own bound) and dies with
                # the backlog
                with acc.lock:
                    if not acc.items:
                        acc.leader_active = False
                        return
                continue
            t_got = time.perf_counter()
            # the slot is ours until a launch takes it: every way out
            # of this iteration that launched nothing gives it back
            done = None
            batch: list[_Pending] = []
            try:
                batch, more = self._pop(acc, dindex, me)
                # deadline filter: an entry that expired while queued
                # must not consume a kernel lane — and a batch whose
                # EVERY member expired must not launch at all (the
                # clients are gone; the device time would be pure
                # waste). Classification per entry matches the wait
                # paths: request deadline lapsed -> 504, local batch
                # timeout only -> 503 (counters updated inside).
                live = []
                for p in batch:
                    if p.deadline.expired():
                        p.error = self._timeout_error(p.req_deadline)
                        p.event.set()
                    else:
                        live.append(p)
                # our OWN entry resolved by the filter just now
                # (expired while we led): no launch on this thread
                mine_gone = me is not None and me.event.is_set()
                if live and not mine_gone:
                    # the slot wait, read once a launch:
                    # ``batcher.pipeline`` serves each entry for the
                    # part of the wait it was queued through, and
                    # ``batcher.wait`` (_execute) takes the rest of its
                    # time up to the launcher
                    slot_ms = (t_got - t_slot) * 1e3
                    for p in live:
                        p.slot_ms = min(
                            slot_ms, max(0.0, (t_got - p.t_submit) * 1e3)
                        )
                        # ... and each entry's vector takes its part
                        if p.ctx is not None:
                            p.ctx.stages["batcher.pipeline"] += p.slot_ms
                    tracer.observe(
                        "batcher.pipeline",
                        slot_ms,
                        sum(p.slot_ms for p in live) / slot_ms
                        if slot_ms > 0
                        else len(live),
                    )
                    # launch on the launcher pool, wait bounded: a
                    # wedged launch fails this request with 503/504
                    # instead of stranding it (and its admission slot)
                    # forever. The launch stage ends at kernel DISPATCH
                    # (the fetch runs on the fetcher pool, and gives
                    # the slot back).
                    bound = self._bound(me)
                    done = self._launcher.submit(
                        self._run_batch, acc, live, dindex, window_cap,
                        record_cap,
                    )
            except BaseException as e:
                # a failure between pop and dispatch (the launcher
                # closed mid-shutdown) must not strand the popped
                # batch: _run_batch never got it — fail its members
                # here or they wait out their full bounds for a launch
                # that will never happen
                for p in batch:
                    if not p.event.is_set():
                        p.error = e
                        p.event.set()
                raise
            finally:
                if done is None:
                    acc.pipeline.release()
            if done is None:
                if live:
                    # return our own 503/504 at once instead of
                    # blocking this request's thread — and its
                    # admission slot — on other requests' launch. Push
                    # the live remainder back (front) so a drainer
                    # serves it with a slot of its own; if leadership
                    # lapsed at the pop and someone else claimed it
                    # meanwhile, they will pop the push-back themselves
                    # — never spawn a second leader.
                    with acc.lock:
                        acc.items = live + acc.items
                        spawn = more or not acc.leader_active
                        acc.leader_active = True
                    if spawn:
                        self._spawn_drainer(
                            acc, dindex, window_cap, record_cap
                        )
                    return
                # nothing to launch: the queue emptied while we waited,
                # or every popped entry had expired
                if more:
                    continue
                return
            if not done.wait(bound):
                # the launch may still complete: its members keep
                # their own bounded event waits and get results or
                # their own expiry — only this serving loop gives
                # up. Leadership (held iff items remained at the
                # pop) passes to a fresh drainer so queued items
                # are served the moment the slow launch frees the
                # device, instead of stalling until the next
                # submit; when more was False it was already
                # released, and a NEW leader may hold it now —
                # don't clobber that.
                if more:
                    self._handoff_or_release(
                        acc, dindex, window_cap, record_cap
                    )
                if me is None or me.event.is_set():
                    # re-check live, not a pre-launch snapshot: the
                    # launch may have delivered our answer right at
                    # the bound — return it rather than miscast a
                    # served request as an error
                    return
                raise self._timeout_error(req_deadline)
            if me is not None:
                # our own entry was in that batch (the leading
                # request is always in the FIRST pop): its result
                # (or error) arrives via the fetch stage and
                # submit_many's bounded event wait — hand any
                # backlog to a drainer and stop serving other
                # requests' batches on this request's clock
                if more:
                    self._handoff_or_release(
                        acc, dindex, window_cap, record_cap
                    )
                return
            if not more:
                return

    def _pop(self, acc, dindex, me) -> tuple[list, bool]:
        """One launch's entries off the queue, and whether any remain
        (leadership is released here when none do). Called with a fetch
        slot in hand, so what is taken is launched next."""
        with acc.lock:
            # lane-ordered pop: when the queue holds both lanes,
            # interactive entries ride the next launch ahead of bulk
            # ones (stable within a lane, so FIFO fairness survives).
            # Only matters when the backlog exceeds one launch —
            # entries sharing a launch share its latency regardless of
            # order. The leading request's own entry stays first (the
            # serving loop assumes `me` rides the first pop).
            if len(acc.items) > 1:
                head = 1 if me is not None and acc.items[0] is me else 0
                tail = acc.items[head:]
                if any(p.lane == "bulk" for p in tail) and any(
                    p.lane != "bulk" for p in tail
                ):
                    # aged bulk entries keep their FIFO spot: a steady
                    # interactive stream re-sorting every pop must not
                    # displace an admitted bulk entry until its
                    # deadline (the admission queue's starvation
                    # escape, mirrored here)
                    now_pc = time.perf_counter()
                    exempt_s = self.BULK_SORT_STARVATION_MS / 1e3
                    tail.sort(
                        key=lambda p: p.lane == "bulk"
                        and now_pc - p.t_submit < exempt_s
                    )
                    acc.items[head:] = tail
            # cap by FLATTENED spec count, not submissions (a fused
            # submit_many entry carries k specs), at the padded shape
            # the head entry already pays for: entries ride in order
            # while the launch still fits the size a launch of the head
            # alone would run at (the index's own choice:
            # ops.launch_capacity), and the next one opens the next
            # launch. So a batched launch costs the device what a
            # launch of one costs, and never compiles a shape
            # mid-request that warm-up did not (the r4 soak tail). A
            # single oversized submission still goes alone.
            n_specs = n_take = 0
            fits = self.max_batch
            for p in acc.items:
                if not n_take:
                    fits = min(
                        fits, launch_capacity(dindex, len(p.specs))
                    )
                elif n_specs + len(p.specs) > fits:
                    break
                n_take += 1
                n_specs += len(p.specs)
                if n_take >= self.max_batch:
                    break
            batch = acc.items[:n_take]
            acc.items = acc.items[n_take:]
            more = bool(acc.items)
            if not more:
                acc.leader_active = False
        return batch, more

    def _handoff_or_release(self, acc, dindex, window_cap, record_cap):
        """Pass held leadership to a transient daemon drainer when
        backlog remains, else release it — atomically, so no window
        exists in which a new submit would elect a second leader."""
        with acc.lock:
            handoff = bool(acc.items)
            if not handoff:
                acc.leader_active = False
        if handoff:
            self._spawn_drainer(acc, dindex, window_cap, record_cap)

    def _spawn_drainer(self, acc, dindex, window_cap, record_cap) -> None:
        threading.Thread(
            target=self._drain,
            args=(acc, dindex, window_cap, record_cap),
            name="batch-drain",
            daemon=True,
        ).start()

    def _bound(self, me: _Pending | None) -> float | None:
        """Seconds a serving loop may block on one wait (the slot, the
        launch's dispatch): the leading request's own deadline, a fresh
        default bound for a drainer; None is unbounded."""
        return (
            me.deadline.remaining()
            if me is not None
            else self.default_timeout_s
        )

    def _drain(self, acc, dindex, window_cap, record_cap) -> None:
        """Transient background drainer: continues the leadership loop
        after the electing request returned (daemon thread; dies as
        soon as the accumulator empties or a launch wedges)."""
        try:
            self._serve(acc, dindex, window_cap, record_cap, None, NO_DEADLINE)
        except BaseException as e:  # pragma: no cover - failsafe
            self._fail_queued(acc, e)
        finally:
            # the thread ends here: its reading for its role's sums
            thread_clock.leave()

    def _timeout_error(self, req_deadline) -> BaseException:
        """Bounded-wait expiry, one classification for leader and
        follower: the REQUEST deadline lapsed -> 504 semantics; only
        the local batch timeout -> 503 server-side wedge."""
        if req_deadline.expired():
            with self._stats_lock:
                self._n_expired += 1
            return DeadlineExceeded(
                "request deadline expired waiting for the kernel launch"
            )
        with self._stats_lock:
            self._n_timeouts += 1
        return BatchTimeout(
            "kernel launch did not complete within the submit timeout "
            "(wedged device or saturated launcher)"
        )

    def _run_batch(self, acc, batch, dindex, window_cap, record_cap) -> None:
        """Launcher-thread entry: _execute plus a failsafe so NO batch
        member can be left without a result/error even if result
        distribution itself raises — waiters' bounds are a backstop,
        not the primary delivery mechanism. The batch arrives with its
        accumulator's fetch-pipeline slot (taken by whoever led it): a
        launch that never reached the fetch stage gives it back here."""
        fetching = False
        try:
            fetching = self._execute(
                acc, batch, dindex, window_cap, record_cap
            )
        except BaseException as e:  # pragma: no cover - failsafe
            for p in batch:
                if not p.event.is_set():
                    p.error = e
                    p.event.set()
        finally:
            if not fetching:
                acc.pipeline.release()

    def close(self) -> None:
        """Release the launcher + fetcher pools (long-lived batchers
        only die with their engine; call through VariantEngine.close)."""
        self._launcher.close()
        self._fetcher.close()

    def timing_summary(self) -> dict:
        """Percentiles of the per-request decomposition over the
        stages' bounded rings (utils/trace.py, process-wide like the
        counters): queue_wait_ms (submit -> kernel
        launch; server-side queueing behind in-flight launches) and
        exec_ms (launch -> results; the device dispatch incl. the
        host-device round trip), plus the per-launch stage split —
        encode_ms (host query encoding), launch_ms (async kernel dispatch) and
        fetch_ms (device execution + device-to-host readback).
        client_latency ~= queue_wait + exec + HTTP/materialisation
        overhead — the soak harness reports all of these so tails are
        attributable to a stage."""
        return {
            "queue_wait_ms": tracer.stage_quantiles("batcher.queue_wait"),
            "exec_ms": tracer.stage_quantiles("batcher.exec"),
            "encode_ms": tracer.stage_quantiles("batcher.encode"),
            "launch_ms": tracer.stage_quantiles("batcher.launch"),
            "fetch_ms": tracer.stage_quantiles("batcher.fetch"),
        }

    def occupancy(self) -> dict:
        """{'submits': N, 'launches': M, 'mean_batch': x, 'histogram':
        {submissions_per_launch: count}, 'fused_hist':
        {specs_per_launch: count}, 'launcher': {...}, 'fetcher': {...}}
        — cumulative since construction. ``fused_hist`` differs from
        ``histogram`` exactly when fused multi-shard submissions rode
        along (one submission carrying k specs); ``launcher``/
        ``fetcher`` report pool depth (threads spawned, tasks queued)
        under stable keys for /metrics."""
        with self._stats_lock:
            hist = dict(sorted(self._batch_hist.items()))
            fused_hist = dict(sorted(self._fused_hist.items()))
            launches = sum(hist.values())
            total = sum(k * v for k, v in hist.items())
            out = {
                "submits": self._n_submits,
                "specs": self._n_specs,
                "launches": launches,
                "mean_batch": round(total / launches, 2) if launches else 0.0,
                "histogram": hist,
                "fused_hist": fused_hist,
                "expired": self._n_expired,
                "timeouts": self._n_timeouts,
            }
        out["launcher"] = self._launcher.depth()
        out["fetcher"] = self._fetcher.depth()
        return out

    def register_metrics(self, registry) -> None:
        """Register this batcher's typed instruments (the occupancy /
        timing dicts' contents, under their historical ``/metrics``
        keys as dotted names). Collection reads the same
        ``occupancy()`` / ``timing_summary()`` state the soak harness
        consumes, so the two surfaces cannot drift.

        The 17 instruments share ONE briefly-cached snapshot per
        render pass: ``timing_summary()`` copies five timing rings
        (up to ``trace.STAGE_RING`` floats each) and runs percentile
        sorts under the hot-path stats lock — recomputing it per
        instrument would make every Prometheus scrape contend with
        request serving 17 times over."""
        snap_lock = threading.Lock()
        snap = {"t": 0.0, "occ": None, "timing": None}

        def snapshot():
            now = time.monotonic()
            with snap_lock:
                if snap["occ"] is None or now - snap["t"] > 0.25:
                    snap["occ"] = self.occupancy()
                    snap["timing"] = self.timing_summary()
                    snap["t"] = now
                return snap["occ"], snap["timing"]

        def occ(*path):
            def collect():
                v = snapshot()[0]
                for part in path:
                    v = v[part]
                return v

            return collect

        def hist(name):
            return lambda: {
                str(k): v for k, v in snapshot()[0][name].items()
            }

        def timing(name):
            return lambda: snapshot()[1][name]

        registry.counter(
            "batcher.submits", "micro-batch submissions", fn=occ("submits")
        )
        registry.counter(
            "batcher.specs", "flattened query specs", fn=occ("specs")
        )
        registry.counter(
            "batcher.launches", "kernel launches", fn=occ("launches")
        )
        registry.gauge(
            "batcher.mean_batch",
            "mean submissions per launch",
            fn=occ("mean_batch"),
        )
        registry.counter(
            "batcher.expired",
            "submits whose request deadline lapsed before launch",
            fn=occ("expired"),
        )
        registry.counter(
            "batcher.timeouts",
            "submits that timed out waiting for a launch",
            fn=occ("timeouts"),
        )
        registry.counter(
            "batcher.histogram",
            "launches by submissions-per-launch",
            label="batch_size",
            fn=hist("histogram"),
        )
        registry.counter(
            "batcher.fused_hist",
            "launches by flattened specs-per-launch",
            label="specs_per_launch",
            fn=hist("fused_hist"),
        )
        registry.gauge(
            "batcher.launcher.threads", fn=occ("launcher", "threads")
        )
        registry.gauge(
            "batcher.launcher.queued", fn=occ("launcher", "queued")
        )
        registry.gauge(
            "batcher.fetcher.threads", fn=occ("fetcher", "threads")
        )
        registry.gauge(
            "batcher.fetcher.queued", fn=occ("fetcher", "queued")
        )
        registry.gauge(
            "batcher.queue_wait_ms",
            "submit -> kernel launch wait quantiles",
            label="quantile",
            fn=timing("queue_wait_ms"),
        )
        registry.gauge(
            "batcher.exec_ms",
            "launch -> results quantiles",
            label="quantile",
            fn=timing("exec_ms"),
        )
        registry.gauge(
            "batcher.encode_ms",
            "host query-encode quantiles",
            label="quantile",
            fn=timing("encode_ms"),
        )
        registry.gauge(
            "batcher.launch_ms",
            "async kernel-dispatch quantiles",
            label="quantile",
            fn=timing("launch_ms"),
        )
        registry.gauge(
            "batcher.fetch_ms",
            "device execute + readback quantiles",
            label="quantile",
            fn=timing("fetch_ms"),
        )
        # the end-to-end queue-wait decomposition as ONE labeled
        # histogram (batch_wait per submission; encode/launch/device/
        # fetch once per launch): dashboards see which stage eats the
        # latency budget without diffing five quantile gauges
        self._stage_hist = registry.histogram(
            "batcher.stage_ms",
            "per-stage latency decomposition "
            "(batch_wait/encode/launch/device/fetch)",
            label="stage",
        )

    def _execute(self, acc, batch, dindex, window_cap, record_cap):
        """LAUNCH stage (launcher thread): flatten the batch's specs,
        encode and dispatch ONE kernel launch, then hand the in-flight
        device futures to the fetcher pool. Returning here (which sets
        the leader's ``done`` event) means only that the launch is
        dispatched — results are delivered by :meth:`_fetch_batch`, so
        host encode of the next batch overlaps device execution of
        this one. The batch comes with its slot of the accumulator's
        bounded fetch pipeline (taken before the pop, ``_serve``), so
        at most ``pipeline_depth`` batches are ever
        launched-but-unfetched. True once the fetch stage has the
        launch and will give the slot back; otherwise the caller
        (``_run_batch``) does."""
        specs: list = []
        offsets: list[int] = []
        for p in batch:
            offsets.append(len(specs))
            specs.extend(p.specs)
        shard_ids = None
        if batch and batch[0].shard_ids is not None:
            shard_ids = [s for p in batch for s in p.shard_ids]
        # one clock reading per boundary from here on; each feeds the
        # chain stage that ends there, the composite behind the old
        # /debug/status key, the histogram and the cost vector alike
        n = len(batch)
        t_launch = time.perf_counter()
        with self._stats_lock:
            self._batch_hist[n] = self._batch_hist.get(n, 0) + 1
            self._fused_hist[len(specs)] = (
                self._fused_hist.get(len(specs), 0) + 1
            )
        stage_hist = self._stage_hist
        for p in batch:
            wait_ms = (t_launch - p.t_submit) * 1e3
            tracer.observe(
                "batcher.wait", wait_ms - p.slot_ms, ctxs=(p.ctx,)
            )
            tracer.observe("batcher.queue_wait", wait_ms)
            if stage_hist is not None:
                stage_hist.observe(wait_ms, label_value="batch_wait")
            # batch wait is queued time on this request's clock — cost-
            # attributed like the fair-queue wait (per-submission ctx:
            # this runs on the launcher thread, not the request's)
            charge_cost_to(p.ctx, queue_wait_ms=wait_ms)
        # the batch leader's request context rides the launch thread
        # (ambient, like deadlines): the flight recorder stamps launch
        # records — and a mid-request device.compile journal event —
        # with the trace id of the request that paid for the launch.
        # Cost attribution stays per-submission via the explicit ctx.
        # ... and every entry's context takes the launch's stages into
        # its own vector, as the stages' req_ms counts them n times
        ctxs = [p.ctx for p in batch if p.ctx is not None]
        lead_ctx = ctxs[0] if ctxs else None
        try:
            with request_context(lead_ctx), span(
                "serving.microbatch"
            ) as sp, tracer.serving(n, ctxs):
                # chaos site: a raised fault takes the existing
                # launch-failure path (every waiter gets the error)
                fault_point("kernel.launch")
                # shape bucketing happens INSIDE the kernels (the XLA
                # path pads to the active tier ladder's rungs, the
                # scatter path to its fixed chunk slots) — pre-padding
                # here doubled the
                # copy and turned pad rows into extra scatter dispatches
                enc = encode_queries(specs, shard_ids=shard_ids)
                t_enc = time.perf_counter()
                pending = run_queries_auto(
                    dindex,
                    enc,
                    window_cap=window_cap,
                    record_cap=record_cap,
                    async_fetch=True,
                )
                t_disp = time.perf_counter()
                sp.note(batch=len(specs))
        except BaseException as e:
            for p in batch:
                p.error = e
                p.event.set()
            return False
        # the old keys' measuring points: encode = encode_queries,
        # launch = the whole of run_queries_auto (for a family that
        # fetches inside its call, the device run and readback too);
        # the stages of that call are taken inside ops/
        encode_ms = (t_enc - t_launch) * 1e3
        launch_ms = (t_disp - t_enc) * 1e3
        tracer.observe("batcher.encode", encode_ms, n)
        tracer.observe("batcher.launch", launch_ms, n)
        # the launch's flight-recorder record gets the host encode
        # stage (the kernel seam only sees pre-encoded arrays; fetch ms
        # is attached by the pending handle's own fetch)
        note_device_stage(
            getattr(pending, "flight_seq", None), encode_ms=encode_ms
        )
        if stage_hist is not None:
            stage_hist.observe(encode_ms, label_value="encode")
            stage_hist.observe(launch_ms, label_value="launch")
        try:
            self._fetcher.submit(
                self._fetch_batch,
                acc,
                batch,
                offsets,
                pending,
                t_launch,
                t_disp,
            )
        except BaseException as e:
            # fetcher closed mid-shutdown: the dispatched launch has no
            # fetcher — fail the batch here or its members wait out
            # their full bounds for results that will never arrive
            for p in batch:
                if not p.event.is_set():
                    p.error = e
                    p.event.set()
            return False
        return True

    def _fetch_batch(
        self, acc, batch, offsets, pending, t_launch, t_disp
    ) -> None:
        """FETCH stage (fetcher thread): block on the device results,
        hand each submission its row-slice, release the pipeline slot."""
        try:
            n = len(batch)
            t_fetch = time.perf_counter()
            ctxs = [p.ctx for p in batch if p.ctx is not None]
            tracer.observe(
                "batcher.fetch_wait", (t_fetch - t_disp) * 1e3, n, ctxs
            )
            with tracer.serving(n, ctxs):
                res = pending.fetch()
            t_done = time.perf_counter()
            exec_ms = (t_done - t_launch) * 1e3
            fetch_ms = (t_done - t_disp) * 1e3
            tracer.observe("batcher.fetch", fetch_ms, n)
            for _ in batch:
                tracer.observe("batcher.exec", exec_ms)
            stage_hist = self._stage_hist
            if stage_hist is not None:
                # device = launch -> results (exec), fetch = the
                # readback tail of it; once per launch
                stage_hist.observe(exec_ms, label_value="device")
                stage_hist.observe(fetch_ms, label_value="fetch")
            # device-launch cost attribution: the launch's measured
            # execute time (launch -> results, the device's busy span
            # for this program) pro-rated to each submission by its
            # share of the flattened specs — the whole launch is always
            # attributed, so sum(shares) == exec time exactly
            n_specs = sum(len(p.specs) for p in batch) or 1
            for p, off in zip(batch, offsets):
                sl = slice(off, off + len(p.specs))
                p.result = QueryResults(
                    exists=res.exists[sl],
                    call_count=res.call_count[sl],
                    n_variants=res.n_variants[sl],
                    all_alleles_count=res.all_alleles_count[sl],
                    n_matched=res.n_matched[sl],
                    overflow=res.overflow[sl],
                    rows=res.rows[sl],
                )
                charge_cost_to(
                    p.ctx,
                    device_us=exec_ms * 1e3 * len(p.specs) / n_specs,
                )
                p.t_ready = t_done
                p.event.set()
        except BaseException as e:
            for p in batch:
                if not p.event.is_set():
                    p.error = e
                    p.event.set()
        finally:
            acc.pipeline.release()
