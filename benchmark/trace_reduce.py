"""From a profiler trace to device busy time, operations, gaps and kernels.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
JAX alone) into plain event tuples; ``reduce_events`` is pure Python over
those tuples, so that its check runs on the small recorded trace kept
beside it (``testdata/trace_events.json``) with no profiler at all.

An event is ``[plane, line, name, start_ns, duration_ns]``. A device plane
is named ``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
launch of a compiled program (``jit_<function>(<fingerprint>)``), its
``XLA Ops`` line the operations inside them. Host planes hold one line
per thread.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
OP_NAME_CHARS = 96
#: host events that say nothing about what the host was doing
DULL_HOST = re.compile(r"^(bench\.traced_window|ThreadpoolListener|\$)")


def load_xplane(path) -> list:
    from jax.profiler import ProfileData

    events = []
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(
                    [plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)]
                )
    return events


def module_name(name: str) -> str:
    """``jit_scatter_query(8412...)`` -> ``jit_scatter_query``."""
    return re.sub(r"\(\d+\)$", "", name)


def union_ns(intervals: list) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def reduce_events(events: list) -> dict:
    """busy_s (union of device-operation intervals, averaged over the device
    planes), window_s (the traced span, by the harness's own annotation
    when it is there), the operations that took most device time, the
    longest gaps between launches named by the host event that covers most
    of each, and per compiled program its launches and device seconds."""
    planes: dict[str, dict] = {}
    host = []
    window = None
    for plane, line, name, start, dur in events:
        if DEVICE_PLANE.match(plane):
            planes.setdefault(plane, {}).setdefault(line, []).append((start, start + dur, name))
        else:
            if name == "bench.traced_window":
                window = (start, start + dur)
            elif dur > 0 and not DULL_HOST.match(name):
                host.append((start, start + dur, name))
    if window is None:
        spans = [(s, e) for p in planes.values() for evs in p.values() for s, e, _ in evs]
        window = (min(s for s, _ in spans), max(e for _, e in spans)) if spans else (0, 0)
    w0, w1 = window
    clip = lambda s, e: (max(s, w0), min(e, w1))
    busy, ops, modules = [], {}, {}
    gaps = []
    for lines in planes.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULE_LINE) or []
        busy.append(union_ns([clip(s, e) for s, e, _ in op_events if e > w0 and s < w1]))
        for s, e, name in op_events:
            if e > w0 and s < w1:
                # an operation's name is its whole HLO line: the head says which
                ops[name[:OP_NAME_CHARS]] = ops.get(name[:OP_NAME_CHARS], 0) + (e - s)
        launches = sorted((s, e, n) for s, e, n in lines.get(MODULE_LINE, []) if e > w0 and s < w1)
        for s, e, name in launches:
            m = modules.setdefault(module_name(name), [0, 0])
            m[0] += 1
            m[1] += e - s
        edge = w0
        for s, e, name in launches:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if w1 > edge:
            gaps.append((edge, w1))
    n_dev = max(1, len(planes))
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover: dict[str, int] = {}
        for s, e, name in host:
            o = min(e, b) - max(s, a)
            if o > 0:
                cover[name] = cover.get(name, 0) + o
        what = max(cover, key=cover.get) if cover else "no host event traced"
        named.append([what, (b - a) / 1e9])
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(planes),
        "device_ops": [[n, d / 1e9] for n, d in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": named,
        "modules": {n: {"launches": c, "seconds": d / 1e9} for n, (c, d) in modules.items()},
    }
