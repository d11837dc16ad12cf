"""One yardstick: ``benchmark/run.py``. The pre-chip measurement plane
left the tree with PR 29; nothing that stays may still name it, or a
reader is sent to a judge that no longer exists."""

import os
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# modules, functions, environment names and record files PR 29 removed
REMOVED = (
    "bench.py",
    "bench_history",
    "bench_cache",
    "cached_synthetic_shard",
    "run_concurrent_soak",
    "device_time_probe",
    "device_plane_probe",
    "_probe_rep",
    "_probe_one_tier",
    "roofline_fraction",
    "device_qps",
    "gather_gb_per_s",
    "BENCH_ROWS",
    "BENCH_SAMPLES",
    "BENCH_PLANE_ROWS",
    "BENCH_CO_ROWS",
    "BENCH_BUDGET_S",
    "BENCH_CACHE",
    "BENCH_r0",
    "INGEST_r0",
    "METADATA_r0",
    "MULTICHIP_r0",
)

# the PR's own records may name what went
EXEMPT = {"CHANGES.md", "ISSUE.md", "tests/test_tree_hygiene.py"}
# these name it in their history sections only
HISTORY = {"PERF.md": "## 6.", "ROADMAP.md": "## Recent"}
SKIPPED_DIRS = {"beacon_data", "chiprun_out", "__pycache__"}
CHECKED = (".py", ".md", ".yml")


def _checked_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".") and d not in SKIPPED_DIRS
        ]
        for name in files:
            if name.endswith(CHECKED) or name == "Dockerfile":
                yield Path(root) / name
    # the walk skips dot-directories; the builders' notes live in one
    skill = REPO / ".claude" / "skills" / "verify" / "SKILL.md"
    if skill.exists():
        yield skill


def _without_section(text: str, heading: str) -> str:
    """``text`` less the section ``heading`` opens, up to the next
    heading of the same level."""
    start = text.index("\n" + heading)
    end = text.find("\n## ", start + 1)
    return text[:start] + (text[end:] if end >= 0 else "")


def test_nothing_names_the_removed_measurement_plane():
    offenders = []
    for path in _checked_files():
        rel = path.relative_to(REPO).as_posix()
        if rel in EXEMPT:
            continue
        text = path.read_text(errors="replace")
        if rel in HISTORY:
            text = _without_section(text, HISTORY[rel])
        offenders += [
            f"{rel}: {name}" for name in REMOVED if name in text
        ]
    assert not offenders, offenders
    for gone in ("bench.py", "tools/bench_history.py",
                 "sbeacon_tpu/harness/bench_cache.py"):
        assert not (REPO / gone).exists(), gone
    assert not list(REPO.glob("*_r0*.json"))
