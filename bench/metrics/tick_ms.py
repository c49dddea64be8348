"""Mean host-clock time of a gateway tick, from staging to finish
(`StudyGateway.stats[].latency_ms`), over the window's ticks."""


def read(ctx):
    ticks = ctx.ticks
    if not ticks:
        return None
    return sum(t["latency_ms"] for t in ticks) / len(ticks)
