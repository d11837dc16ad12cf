"""How a labelled series of the program spreads over its first label.

``args``: ``path``, a dotted path into the ``/metrics`` JSON that names a
series with labels (``device.launches_by_chip`` is ``{chip: n}``,
``device.resident_bytes`` is ``{chip: {kind: bytes}}``: whatever lies under
a first-label value is summed); ``delta`` true takes each value's
difference over the measured window (a counter), false its reading after
the window (a gauge); ``what``: ``min_over_max`` (the least value over the
largest: 1 when the labels share alike) or ``max``; ``scale`` multiplies.
A program without the series, or with nothing counted, gives None.
"""


from readers.counter_ratio import at, leaves


def read(args: dict, ctx: dict):
    after = at(ctx["after"]["metrics"], args["path"])
    if not isinstance(after, dict) or not after:
        return None
    before = at(ctx["before"]["metrics"], args["path"]) if args.get("delta") else None
    values = [
        leaves(node) - (leaves(before.get(label)) if isinstance(before, dict) else 0.0)
        for label, node in after.items()
    ]
    top = max(values)
    if top <= 0:
        return None
    if args["what"] == "min_over_max":
        return min(values) / top * float(args.get("scale", 1))
    if args["what"] == "max":
        return top * float(args.get("scale", 1))
    raise ValueError(f"unknown label_spread reading {args['what']!r}")
