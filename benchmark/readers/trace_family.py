"""Numbers of the traced window: the device's idle share, and the kernel
time of the cell's dominant program family with its share of the HBM
roofline.

``args.what``: ``idle_share`` | ``ms_per_launch`` | ``hbm_share``;
``args.family`` names the program family. Its compiled programs are
matched in the trace by the patterns of ``benchmark/rooflines/<family>.py``,
which also gives the least bytes one launch must move.
"""

import re


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    if args["what"] == "idle_share":
        if not trace["devices"]:
            return None
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    mod = ctx["roofline"](args["family"])
    launches = seconds = 0.0
    for name, m in trace["modules"].items():
        if any(re.search(p, name) for p in mod.MODULES):
            launches += m["launches"]
            seconds += m["seconds"]
    if not launches or seconds <= 0:
        return None
    if args["what"] == "ms_per_launch":
        return seconds / launches * 1e3
    if args["what"] == "hbm_share":
        if not ctx.get("peaks"):
            return None
        least = mod.least_bytes_per_launch(ctx) * launches
        return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / seconds
    raise ValueError(f"unknown trace_family reading {args['what']!r}")
