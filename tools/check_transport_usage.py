#!/usr/bin/env python
"""Static transport lint for the coordinator-worker data plane.

After ISSUE 5 every coordinator->worker HTTP call rides the pooled
keep-alive transport (``sbeacon_tpu/parallel/transport.py``). A future
call site that reaches for ``urllib.request.urlopen`` silently
regresses to one TCP handshake per call — exactly the per-call tail
that PR removed — so this lint fails when a direct ``urlopen`` use
appears anywhere under ``sbeacon_tpu/`` outside the allowlist:

- ``parallel/transport.py`` — the owner (also hosts the unpooled
  ``urllib_*`` fallbacks kept as injectable seams),
- ``io/sources.py`` and ``metadata/resolvers.py`` — external-service
  clients (object-store ranged GETs, OLS/Ontoserver resolution): not
  the worker data plane, each manages its own connection strategy.

Since ISSUE 6 the dispatcher keeps a FULL replica list per dataset and
every worker ``/search`` routing decision goes through the replica
selector (``dispatch.ReplicaRouter.pick`` — power-of-two-choices,
breaker-aware, failover-capable). A call site that indexes the route
table directly (``self._routes[ds]`` / ``routes()[ds]`` /
``replica_table()[ds]``) silently regresses to first-replica routing
with no failover — exactly the dead-worker unavailability that PR
removed — so a second pattern rejects route-table subscripts anywhere
under ``sbeacon_tpu/`` (no allowlist: ``dispatch.py`` itself routes
through the router).

Run directly (``python tools/check_transport_usage.py``) or via the
tier-1 test ``tests/test_transport.py::test_transport_usage_lint``
(mirroring ``tools/check_metric_names.py``).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

PKG = Path(__file__).resolve().parent.parent / "sbeacon_tpu"

#: package-relative paths allowed to touch urllib.request.urlopen
ALLOWED = {
    "parallel/transport.py",
    "io/sources.py",
    "metadata/resolvers.py",
}

#: direct urlopen use in any spelling: qualified calls and imports that
#: would let a bare ``urlopen(`` appear later
PATTERN = re.compile(
    r"urllib\s*\.\s*request\s*\.\s*urlopen"
    r"|(?<![\w.])request\.urlopen\s*\("
    r"|from\s+urllib\.request\s+import\s+[^\n]*\burlopen\b"
)

#: route-table subscripts on the worker /search plane: routing must go
#: through the replica selector (ReplicaRouter.pick) so failover and
#: p2c load spreading apply — indexing the table pins first-replica
#: routing with no failover. Applies everywhere (no allowlist).
ROUTE_PATTERN = re.compile(
    r"\._routes\s*\["
    r"|\.routes\(\s*[^)]*\)\s*\["
    r"|\.replica_table\(\s*[^)]*\)\s*\["
)


def scan(root: Path = PKG) -> list[str]:
    """["file:line: matched text"] for every disallowed urlopen use or
    direct route-table subscript."""
    hits = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        src = path.read_text()
        if rel not in ALLOWED:
            for m in PATTERN.finditer(src):
                line = src[: m.start()].count("\n") + 1
                hits.append(
                    f"sbeacon_tpu/{rel}:{line}: {m.group(0)!r} — route "
                    "worker-plane HTTP through parallel/transport.py "
                    "(pooled keep-alive), or add this file to the "
                    "documented allowlist"
                )
        for m in ROUTE_PATTERN.finditer(src):
            line = src[: m.start()].count("\n") + 1
            hits.append(
                f"sbeacon_tpu/{rel}:{line}: {m.group(0)!r} — pick worker "
                "/search targets via the replica selector "
                "(dispatch.ReplicaRouter.pick), never by indexing the "
                "route table (loses failover and p2c routing)"
            )
    return hits


def main() -> int:
    hits = scan()
    if hits:
        for h in hits:
            print(f"ERROR: {h}")
        return 1
    # the owner must still exist — an empty scan because transport.py
    # was deleted would be a false pass
    if not (PKG / "parallel" / "transport.py").exists():
        print("ERROR: sbeacon_tpu/parallel/transport.py is missing")
        return 1
    print("ok: no direct urlopen use outside the transport allowlist")
    return 0


if __name__ == "__main__":
    sys.exit(main())
