"""Where the slowest twentieth of the window's requests spent more than its middle fifth.

The program classes every status-200 request of a tracked route against the
route's running quantiles of ``meta.elapsedTimeMs`` (``utils/trace.py``
``TailFold``): *tail* at or over the p95, *body* between the p40 and the p60,
and sums each side's own stage vectors into ``request.tail_ms{stage}`` /
``request.body_ms{stage}`` beside ``request.tail_count`` / ``request.body_count``
(``/metrics``; label values: the chain's stages and ``unnamed``, the time between
them, so a side's labels add up to its requests' elapsed time).

``args``: ``stages``, the label values to add up (left out: every label the
program serves). Over the window's counter differences: the tail's mean
milliseconds a request in those stages, less the body's. A label value holds a
dot, so it is looked up whole (``counter_ratio``'s dotted paths cannot). A
program without the series, or a side with no request in the window, gives None.
"""


def side_mean(ctx: dict, side: str, stages):
    """Mean milliseconds a request of one side in ``stages`` over the window."""
    after = ctx["after"]["metrics"].get("request") or {}
    before = ctx["before"]["metrics"].get("request") or {}
    ms, count = f"{side}_ms", f"{side}_count"
    if not isinstance(after.get(ms), dict) or count not in after:
        return None
    n = float(after[count]) - float(before.get(count, 0.0))
    if n <= 0:
        return None
    was = before.get(ms) or {}
    names = after[ms] if stages is None else stages
    return sum(float(after[ms].get(s, 0.0)) - float(was.get(s, 0.0)) for s in names) / n


def read(args: dict, ctx: dict):
    stages = args.get("stages")
    tail = side_mean(ctx, "tail", stages)
    body = side_mean(ctx, "body", stages)
    if tail is None or body is None:
        return None
    return tail - body
