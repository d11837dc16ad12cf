"""What a request spends outside the server's own clock: per request, the
client's latency minus the envelope's ``meta.elapsedTimeMs``; the median
over every request of the window that carried one."""


def read(args: dict, ctx: dict):
    gaps = sorted(r[1] - r[2] for r in ctx["records"] if r[2] >= 0)
    if not gaps:
        return None
    return gaps[len(gaps) // 2]
