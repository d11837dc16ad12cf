"""A quantile of the program's own stage clocks (``/debug/status`` stages).

``args``: ``series`` (stage names, summed) and ``q`` (p50, p95 or p99).
The program keeps each stage's last 65,536 samples and never resets them,
so the quantile is over the run's warm-up traffic and its window together;
the warm-up is a few hundred requests against the window's thousands.
"""


def read(args: dict, ctx: dict):
    total = 0.0
    for name in args["series"]:
        stage = ctx["after"]["stages"].get(name) or {}
        if args["q"] not in stage:
            return None
        total += float(stage[args["q"]])
    return total
