#!/usr/bin/env python
"""Static launch-recording lint (ISSUE 14 satellite).

Device-launch accounting used to live in unlocked module globals
(``mesh.N_LAUNCHES += 1``, ``kernel.N_LAUNCHES += 1``,
``scatter_kernel.N_DISPATCHES += 1``) — read-modify-write races across
request threads on real accelerators, and a counter a new kernel could
silently fork or forget. Every launch now reports through ONE seam,
``telemetry.DeviceFlightRecorder.record_launch`` (which also feeds the
launch ring and the compile tracker), and the old names are module
``__getattr__`` properties reading the recorder.

This lint keeps it that way:

- NO module under ``sbeacon_tpu/`` may assign or augment a
  launch-counter name (``N_LAUNCHES`` / ``N_DISPATCHES``) — at module
  scope, inside a function, or via a ``global`` declaration. A
  reintroduced direct increment is exactly the racy bypass this lint
  exists to stop;
- every module that dispatches compiled device programs (the three
  kernel seams) must keep its module ``__getattr__`` back-compat
  property AND call the recorder seam (``record_device_launch`` /
  ``record_launch``) at least once — a new kernel family cloned from
  one of these files cannot silently drop out of the flight recorder;
- the L0 delta-tail mini-index (ISSUE 15) must stay inside the
  recorded seam: no module other than ``ops/kernel.py`` may call the
  jitted ``_query_batch`` / ``_query_batch_donated`` entries directly
  (a dispatch bypassing ``run_queries`` would be invisible to the
  flight recorder), the ``L0DeviceIndex`` class must pin
  ``flight_family = "fused_l0"`` (its launches are attributable
  separately from the base fused stack), and
  ``telemetry.DEVICE_FAMILIES`` must carry the family.

It also carries the RUNTIME warmup-ladder parity check
(``lint_warmup_ladder``, ISSUE 17 satellite): given a flight-recorder
compile snapshot and the rungs the active ``TierLadder`` serves, every
(family, rung) cell must hold a warmup-stamped compile, so
``device.mid_request_compiles`` stays zero for any batch the ladder
can emit. The static ``main()`` pass cannot observe compiles, so this
check runs from ``tests/test_telemetry.py`` against a warmed engine.

Run directly (``python tools/check_launch_recording.py``) or via the
tier-1 test ``tests/test_telemetry.py::test_launch_recording_lint``.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

PKG = Path(__file__).resolve().parent.parent / "sbeacon_tpu"

#: the launch-counter names whose direct mutation is forbidden
COUNTER_NAMES = frozenset({
    "N_LAUNCHES",
    "N_DISPATCHES",
})

#: the modules that dispatch compiled device programs: each must keep
#: its module __getattr__ property seam and report through the recorder
KERNEL_SEAMS = (
    "ops/kernel.py",
    "ops/scatter_kernel.py",
    "parallel/mesh.py",
)

#: the recorder entry points a kernel seam must call
RECORD_CALLS = frozenset({"record_device_launch", "record_launch"})

#: the jitted query-batch entries: only their own module (the recorded
#: run_queries seam) may invoke them — an L0 (or any) dispatch calling
#: one directly would launch device programs the recorder never sees.
#: The donated variant (ISSUE 17) is the same program with buffer
#: donation and must stay behind the same door.
JIT_ENTRY = "_query_batch"
JIT_ENTRIES = frozenset({"_query_batch", "_query_batch_donated"})
JIT_ENTRY_HOME = "ops/kernel.py"


def _target_names(node: ast.AST) -> set[str]:
    """Every name a statement assigns to — bare Names (tuple targets
    included) AND attribute targets (``mod.N_DISPATCHES += 1`` is the
    sneakier variant: the read goes through the module's PEP 562
    recorder property and the write plants a REAL attribute that
    shadows it for every later reader in the process)."""
    out: set[str] = set()
    targets: list = []
    if isinstance(node, ast.Assign):
        targets = node.targets
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    for t in targets:
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                out.add(n.id)
            elif isinstance(n, ast.Attribute):
                out.add(n.attr)
    return out


def lint_module(rel: str, src: str) -> list[str]:
    """Counter-mutation errors for one module's source."""
    errors: list[str] = []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno}: syntax error: {e.msg}"]
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            hit = sorted(_target_names(node) & COUNTER_NAMES)
            if hit:
                errors.append(
                    f"{rel}:{node.lineno}: direct launch-counter "
                    f"assignment to {hit} — route the increment "
                    "through telemetry.record_device_launch (the "
                    "flight-recorder seam owns these counters)"
                )
        elif isinstance(node, ast.Global):
            hit = sorted(set(node.names) & COUNTER_NAMES)
            if hit:
                errors.append(
                    f"{rel}:{node.lineno}: `global {', '.join(hit)}` "
                    "declaration — launch counters are flight-recorder "
                    "state, not module globals"
                )
    return errors


def lint_jit_bypass(rel: str, src: str) -> list[str]:
    """No module outside the kernel seam may call ``_query_batch`` (or
    its donated twin) directly — the recorded ``run_queries`` entry is
    the only door."""
    if rel.replace("\\", "/").endswith(JIT_ENTRY_HOME):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []  # already reported by lint_module
    errors = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.id
            if isinstance(fn, ast.Name)
            else fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if name in JIT_ENTRIES:
            errors.append(
                f"{rel}:{node.lineno}: direct {name} call — "
                "dispatch through ops.kernel.run_queries (the "
                "flight-recorder seam); a bypassed launch is "
                "invisible to /device/status and the compile tracker"
            )
    return errors


def lint_warmup_ladder(snapshot, expected) -> list[str]:
    """Warmup-ladder parity (ISSUE 17 satellite).

    ``snapshot`` is a flight-recorder compile snapshot
    (``DeviceFlightRecorder.compile_snapshot()`` — a dict whose
    ``entries`` list holds ``{key, family, tier, warmup}`` records) or
    a bare entry list. ``expected`` maps each launch family to the
    batch-tier rungs the active ``TierLadder`` can pad a request to.
    Every (family, rung) cell must be covered by a compile stamped
    inside a ``device_warmup_phase`` — an uncovered rung is exactly a
    batch shape that would pay a mid-request compile the first time
    traffic coalesces to it.
    """
    entries = (
        snapshot.get("entries", [])
        if isinstance(snapshot, dict)
        else list(snapshot)
    )
    warm = {
        (e.get("family"), int(e.get("tier", -1)))
        for e in entries
        if e.get("warmup")
    }
    return [
        f"{family}: ladder rung {t} has no warmup-phase "
        "compile — the first request batch padded to this "
        "tier pays a mid-request compile"
        for family in sorted(expected)
        for t in sorted({int(r) for r in expected[family]})
        if (family, t) not in warm
    ]


def expected_warm_rungs(ladder, families=("fused",)) -> dict:
    """The (family → rungs) map ``lint_warmup_ladder`` checks, derived
    from one ``TierLadder``: every family pads a request's batch to a
    serving rung (``ladder.rungs``) and warms them all."""
    return {f: tuple(ladder.rungs) for f in families}


def lint_l0_family(kernel_src: str, telemetry_src: str) -> list[str]:
    """The L0 mini-index must keep its own recorder family: the class
    pins ``flight_family = 'fused_l0'`` (run_queries reads it per
    launch) and telemetry's DEVICE_FAMILIES literal carries it."""
    errors: list[str] = []
    try:
        tree = ast.parse(kernel_src)
    except SyntaxError:
        return []
    fam = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "L0DeviceIndex":
            for stmt in node.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and any(
                        isinstance(t, ast.Name)
                        and t.id == "flight_family"
                        for t in stmt.targets
                    )
                    and isinstance(stmt.value, ast.Constant)
                ):
                    fam = stmt.value.value
    if fam != "fused_l0":
        errors.append(
            "sbeacon_tpu/ops/kernel.py: L0DeviceIndex must pin "
            "flight_family = 'fused_l0' — L0 tail launches must stay "
            "attributable apart from the base fused stack"
        )
    # the DEVICE_FAMILIES tuple itself must carry the family — AST,
    # not a substring scan: quote style must not matter, and a
    # "fused_l0" literal elsewhere in the module must not satisfy it
    families: set = set()
    try:
        for node in ast.walk(ast.parse(telemetry_src)):
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "DEVICE_FAMILIES"
                for t in node.targets
            ):
                if isinstance(node.value, (ast.Tuple, ast.List)):
                    families = {
                        e.value
                        for e in node.value.elts
                        if isinstance(e, ast.Constant)
                    }
    except SyntaxError:
        pass
    if "fused_l0" not in families:
        errors.append(
            "sbeacon_tpu/telemetry.py: DEVICE_FAMILIES lost the "
            "'fused_l0' family the L0 launch seam reports as"
        )
    return errors


def lint_seam(rel: str, src: str) -> list[str]:
    """A kernel-seam module must keep its __getattr__ property and
    call the recorder at least once."""
    errors: list[str] = []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []  # already reported by lint_module
    has_getattr = any(
        isinstance(n, ast.FunctionDef) and n.name == "__getattr__"
        for n in tree.body
    )
    if not has_getattr:
        errors.append(
            f"{rel}: kernel seam lost its module __getattr__ — the "
            "back-compat counter properties (N_LAUNCHES etc.) must "
            "keep reading the flight recorder"
        )
    calls = {
        n.func.id if isinstance(n.func, ast.Name) else n.func.attr
        for n in ast.walk(tree)
        if isinstance(n, ast.Call)
        and isinstance(n.func, (ast.Name, ast.Attribute))
    }
    if not calls & RECORD_CALLS:
        errors.append(
            f"{rel}: kernel seam never calls the flight recorder "
            "(record_device_launch) — its launches would be invisible "
            "to /device/status and the compile tracker"
        )
    return errors


def main() -> int:
    errors: list[str] = []
    checked = 0
    for path in sorted(PKG.rglob("*.py")):
        rel = str(path.relative_to(PKG.parent))
        src = path.read_text()
        errors += lint_module(rel, src)
        errors += lint_jit_bypass(rel, src)
        checked += 1
    for seam in KERNEL_SEAMS:
        path = PKG / seam
        if not path.exists():
            errors.append(f"sbeacon_tpu/{seam}: kernel seam missing")
            continue
        errors += lint_seam(f"sbeacon_tpu/{seam}", path.read_text())
    kernel = PKG / "ops" / "kernel.py"
    telemetry = PKG / "telemetry.py"
    if kernel.exists() and telemetry.exists():
        errors += lint_l0_family(
            kernel.read_text(), telemetry.read_text()
        )
    if errors:
        for e in errors:
            print(f"ERROR: {e}")
        return 1
    print(
        f"ok: {checked} modules free of direct launch-counter "
        f"mutation, {len(KERNEL_SEAMS)} kernel seams report through "
        "the flight recorder"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
