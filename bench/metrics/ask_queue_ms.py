"""Mean time an ask waited in the gateway's queue before a tick took it:
sum(queue_wait_ms x width) / sum(width) over the window's ticks
(`StudyGateway.stats[].queue_wait_ms` is the mean over each tick's served
asks).  Ticks without the key (a program that does not count it) give
None."""

KEY = "queue_wait_ms"


def read(ctx):
    ticks = ctx.ticks
    if not ticks or any(KEY not in t for t in ticks):
        return None
    served = sum(t["width"] for t in ticks)
    if not served:
        return None
    return sum(t[KEY] * t["width"] for t in ticks) / served
