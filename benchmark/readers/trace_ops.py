"""Device time inside named operations of one program family, from the
traced window's own profile: ``trace_reduce.reduce_events`` keeps the ten
operations that took most device time and whole modules, and an
operation as short as an all-reduce of a few words is not among them.

``args.family`` names the program family (its modules by the patterns of
``benchmark/rooflines/<family>.py``), ``args.ops`` the patterns an
operation's name (its HLO line, ``%all-reduce.3 = ...``) must match,
``args.what``: ``ms_per_launch``, the matched operations' device time over
the family's launches, both summed over the chips' planes: the time ONE
chip spends in them a launch. Only operations that run inside a launch of
the family's modules count. The profile is the ``.xplane.pb`` the harness
wrote for this run under its temporary directory (``bench_*/trace``); with
no profile, no launch of the family or no matching operation in it (a
program whose compiler folded the collective away, a rehearsal off the
chip) the reader returns None and the metric is left out.
"""

import re
import tempfile
from pathlib import Path

import trace_reduce


def profile_events():
    """The newest profile a run of this process's harness wrote, as events."""
    files = sorted(
        Path(tempfile.gettempdir()).glob("bench_*/trace/**/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime,
    )
    return trace_reduce.load_xplane(files[-1]) if files else []


def ms_per_launch(events: list, modules: list, ops: list):
    """Pure over event tuples, so that its check needs no profiler."""
    window = next(
        ((s, s + d) for _p, _l, name, s, d in events if name == "bench.traced_window"), None
    )
    inside = lambda s, e: window is None or (e > window[0] and s < window[1])
    launches: dict[str, list] = {}
    for plane, line, name, s, d in events:
        if (
            trace_reduce.DEVICE_PLANE.match(plane)
            and line == trace_reduce.MODULE_LINE
            and inside(s, s + d)
            and any(re.search(p, trace_reduce.module_name(name)) for p in modules)
        ):
            launches.setdefault(plane, []).append((s, s + d))
    n = sum(len(v) for v in launches.values())
    if not n:
        return None
    matched = total = 0
    for plane, line, name, s, d in events:
        if (
            line == trace_reduce.OPS_LINE
            and plane in launches
            and any(re.search(p, name) for p in ops)
            and any(a <= s and s + d <= b for a, b in launches[plane])
        ):
            matched += 1
            total += d
    if not matched:
        return None
    return total / 1e6 / n


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    if args["what"] != "ms_per_launch":
        raise ValueError(f"unknown trace_ops reading {args['what']!r}")
    try:
        events = profile_events()
    except Exception:
        return None
    return ms_per_launch(events, ctx["roofline"](args["family"]).MODULES, args["ops"])
