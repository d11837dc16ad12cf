"""Who held the host in the device's idle gaps: of the seconds of the traced
window's listed gaps (``trace_reduce.reduce_events`` lists the ten longest,
each named by the host event that covers most of it), the share whose name
starts with ``args.prefix``: the program's own stage annotations
(``beacon.<stage>``, ``beacon.gc.gen<n>``), not PJRT's.
"""


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    gaps = (trace or {}).get("idle_gaps") or []
    total = sum(seconds for _name, seconds in gaps)
    if total <= 0:
        return None
    named = sum(seconds for name, seconds in gaps if name.startswith(args["prefix"]))
    return 100.0 * named / total
