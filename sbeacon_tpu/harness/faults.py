"""Deterministic fault injection for chaos tests and the soak harness.

None of the failure paths the resilience layer guards (wedged workers,
kernel-launch exceptions, slow sqlite commits) occur naturally in CI, so
they must be injectable — reproducibly, or a chaos soak that fails once
can never be re-run. Sites in the serving path call
:func:`fault_point` (a no-op until a plan is installed); a
:class:`FaultPlan` names sites, fault kinds, and seeded activation
rules, and :func:`install` arms it process-wide. Decisions are made by
a per-rule ``random.Random`` seeded from ``(plan.seed, site, rule
index)`` over a per-rule hit counter, so for a given call sequence the
same plan activates the same faults every run (thread interleaving can
reorder *which caller* draws activation n, but the activation pattern
over the sequence is fixed).

Instrumented sites:

- ``worker.http`` — coordinator->worker search call
  (``parallel/dispatch.py DistributedEngine._call_worker``); ``detail``
  is the worker URL, so a rule can target one worker with ``match``.
- ``kernel.launch`` — device kernel dispatch (``serving.py``
  micro-batch execute and ``engine.py`` direct path).
- ``sqlite.commit`` — job-table persistence commits
  (``query_jobs.py``): once a transaction of the runner's writer
  thread (``detail`` ``write_jobs``), so ``latency`` here (the
  WAL-checkpoint fsync stalls the r5 soak chased) delays rows and no
  request, and an ``error`` loses that batch's rows to a restart only.
- ``admission.queue`` — the tenant fair-queue admission path
  (``shaping.py FairQueueAdmission.acquire``); ``detail`` is
  ``tenant:lane``, so a rule can target one tenant or lane with
  ``match``. ``latency`` models a slow shaper (contended dispatch),
  ``error`` fails admission outright — both hit BEFORE any slot is
  taken, so no capacity leaks.
- ``compaction.fold`` — the background delta compactor
  (``ingest/service.py DeltaCompactor._fold``). Hit TWICE per fold
  with ``detail`` ``"<dataset>:<vcf>:merge"`` (before the merge/
  persist) and ``"<dataset>:<vcf>:publish"`` (after the atomic save,
  before the engine swap), so ``match`` can crash either side of the
  durability seam. An ``error`` anywhere leaves base + deltas serving
  duplicate-free and the next run completes the fold — the
  ``-m resilience`` test asserts exactly that.
- ``migration:copy`` / ``migration:dual_serve`` / ``migration:verify``
  / ``migration:cutover`` — the live shard-migration controller's four
  phase-entry seams (``parallel/migration.py MigrationController``),
  hit once at each transition with ``detail``
  ``"<dataset>:<source>-><target>"``. An ``error`` at any seam must
  leave the fleet with the source still routed and serving: a copy
  crash resumes on the next run (manifest diff skips adopted
  artifacts), the later seams roll the target back — never a
  half-routed state. The ``-m resilience`` migration suite kills the
  controller at each seam and asserts exactly that.

Fault kinds: ``error`` raises :class:`FaultError`; ``latency`` sleeps
``ms``; ``hang`` sleeps ``ms`` too but defaults much longer — a hang is
only distinguishable from latency by exceeding every caller's deadline,
which is exactly what the resilience tests assert.

Install via code (tests), or ``BEACON_FAULT_PLAN`` (JSON, or ``@path``
to a JSON file) for chaos runs against a deployed server::

    BEACON_FAULT_PLAN='{"seed": 7, "rules": [
        {"site": "worker.http", "kind": "hang", "rate": 0.1, "ms": 60000},
        {"site": "kernel.launch", "kind": "error", "rate": 0.05}]}'
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import threading
import time


class FaultError(RuntimeError):
    """An injected failure (never raised by real code paths)."""


@dataclasses.dataclass(frozen=True)
class FaultRule:
    site: str
    kind: str = "error"  # error | latency | hang
    rate: float = 1.0  # activation probability per eligible hit
    ms: float = 0.0  # latency duration; hang defaults to 60 s
    after: int = 0  # skip the first N hits of this rule's site
    count: int | None = None  # max activations (None = unlimited)
    match: str = ""  # substring filter on the site's detail

    def __post_init__(self):
        if self.kind not in ("error", "latency", "hang"):
            raise ValueError(f"unknown fault kind {self.kind!r}")
        if not (0.0 <= self.rate <= 1.0):
            raise ValueError(f"rate must be in [0, 1], got {self.rate}")


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    rules: tuple[FaultRule, ...] = ()
    seed: int = 0

    @classmethod
    def from_dict(cls, doc: dict) -> "FaultPlan":
        return cls(
            rules=tuple(FaultRule(**r) for r in doc.get("rules", [])),
            seed=int(doc.get("seed", 0)),
        )

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        return cls.from_dict(json.loads(text))

    def dumps(self) -> str:
        return json.dumps(
            {
                "seed": self.seed,
                "rules": [dataclasses.asdict(r) for r in self.rules],
            }
        )


class FaultInjector:
    """Armed plan: per-rule seeded RNG + hit/activation counters."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self._lock = threading.Lock()
        self._rng = [
            random.Random(f"{plan.seed}:{r.site}:{i}")
            for i, r in enumerate(plan.rules)
        ]
        self._hits = [0] * len(plan.rules)
        self._activations = [0] * len(plan.rules)

    def hit(self, site: str, detail: str = "") -> None:
        """Evaluate every rule for ``site``; apply the first that
        activates (one fault per point keeps plans composable)."""
        action: tuple[str, float, str] | None = None
        with self._lock:
            for i, r in enumerate(self.plan.rules):
                if r.site != site:
                    continue
                if r.match and r.match not in detail:
                    continue
                n = self._hits[i]
                self._hits[i] += 1
                if n < r.after:
                    continue
                if r.count is not None and self._activations[i] >= r.count:
                    continue
                # the draw happens for every eligible hit, activated or
                # not, so the decision sequence is a pure function of
                # (seed, site, rule index, hit number)
                draw = self._rng[i].random()
                if draw >= r.rate:
                    continue
                self._activations[i] += 1
                ms = r.ms if r.ms > 0 else (60_000.0 if r.kind == "hang" else 0.0)
                action = (r.kind, ms, f"injected {site} failure (hit {n})")
                break
        if action is None:
            return
        kind, ms, msg = action
        if kind == "error":
            raise FaultError(msg)
        # latency / hang: sleep OUTSIDE the lock so a hung site never
        # blocks other sites' decisions
        time.sleep(ms / 1e3)

    def stats(self) -> dict:
        """Per-rule hit/activation counts (chaos-run observability)."""
        with self._lock:
            return {
                f"{r.site}[{i}]{':' + r.match if r.match else ''}": {
                    "kind": r.kind,
                    "hits": self._hits[i],
                    "activations": self._activations[i],
                }
                for i, r in enumerate(self.plan.rules)
            }


_installed: FaultInjector | None = None


def install(plan: FaultPlan | dict) -> FaultInjector:
    """Arm a plan process-wide; returns the injector (for .stats())."""
    global _installed
    if isinstance(plan, dict):
        plan = FaultPlan.from_dict(plan)
    _installed = FaultInjector(plan)
    return _installed


def uninstall() -> None:
    global _installed
    _installed = None


def installed() -> FaultInjector | None:
    return _installed


def install_from_env(env=None) -> FaultInjector | None:
    """Arm BEACON_FAULT_PLAN if set (JSON, or @path to a JSON file);
    the deployment entries call this so chaos scenarios run against
    real server processes without code changes."""
    env = os.environ if env is None else env
    raw = env.get("BEACON_FAULT_PLAN", "").strip()
    if not raw:
        return None
    if raw.startswith("@"):
        with open(raw[1:]) as f:
            raw = f.read()
    return install(FaultPlan.from_json(raw))


def fault_point(site: str, detail: str = "") -> None:
    """Instrumentation hook: no-op unless a plan is installed."""
    inj = _installed
    if inj is not None:
        inj.hit(site, detail)
