"""Differences of the program's stage accumulators over the measured window.

The program keeps, for every stage of its serving path (``utils/trace.py``
``STAGES``), a monotone ``count``, ``sum_ms`` and ``req_ms`` (the sum of
duration x requests the sample served) and serves them in ``/debug/status``
``stages``. ``args``: ``stages`` (names, summed), ``minus`` (names,
subtracted: a wait that lies inside the stages, as the job table's lock
lies inside ``runner.lookup``), ``field`` (``sum_ms``, or ``req_ms`` for a
coverage), ``per``: ``request`` (requests completed in the
window), ``sample`` (the count difference of ``samples_of``, by default of
the stages themselves) or ``stage`` (the ``sum_ms`` difference of the
stages in ``over``, for a share); ``scale`` multiplies. A stage the program
does not have, or a divisor of zero, gives None.
"""


def delta(ctx: dict, names: list, field: str):
    total = 0.0
    for name in names:
        after = ctx["after"]["stages"].get(name)
        before = ctx["before"]["stages"].get(name)
        if not isinstance(after, dict) or field not in after:
            return None
        total += float(after[field]) - float((before or {}).get(field, 0.0))
    return total


def read(args: dict, ctx: dict):
    field = args.get("field", "sum_ms")
    num = delta(ctx, args["stages"], field)
    inside = delta(ctx, args.get("minus") or [], field)
    if num is not None and inside is not None:
        num -= inside
    else:
        num = None
    if args["per"] == "request":
        den = float(len(ctx["records"]))
    elif args["per"] == "sample":
        den = delta(ctx, args.get("samples_of") or args["stages"], "count")
    elif args["per"] == "stage":
        den = delta(ctx, args["over"], "sum_ms")
    else:
        raise ValueError(f"unknown divisor {args['per']!r}")
    if num is None or den is None or den <= 0:
        return None
    return num / den * float(args.get("scale", 1))
