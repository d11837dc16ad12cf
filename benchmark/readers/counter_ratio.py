"""Differences of the program's counters over the measured window.

``args``: ``num`` and optional ``den``, each a list of dotted paths into
the ``/metrics`` JSON (a path that names a group sums its leaves, so
``device.fallbacks`` adds up every site); ``den_requests`` divides by the
requests completed in the window instead; ``scale`` multiplies.
The counters are process-wide: the run's one process holds one server.
"""


def leaves(node) -> float:
    if isinstance(node, dict):
        return sum(leaves(v) for v in node.values())
    return float(node) if isinstance(node, (int, float)) else 0.0


def at(doc: dict, path: str):
    node = doc
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def delta(ctx: dict, paths: list) -> float:
    return sum(
        leaves(at(ctx["after"]["metrics"], p)) - leaves(at(ctx["before"]["metrics"], p))
        for p in paths
    )


def read(args: dict, ctx: dict):
    num = delta(ctx, args["num"])
    if args.get("den_requests"):
        den = float(len(ctx["records"]))
    elif "den" in args:
        den = delta(ctx, args["den"])
    else:
        den = 1.0
    if den <= 0:
        return None
    return num / den * float(args.get("scale", 1))
