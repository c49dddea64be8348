"""Mean time a tick's finish blocks the host on the device, reading the
round's suggestions back (`StudyGateway.stats[].wait_ms`), over the
window's ticks.  Ticks without the key give None."""

KEY = "wait_ms"


def read(ctx):
    ticks = ctx.ticks
    if not ticks or any(KEY not in t for t in ticks):
        return None
    return sum(t[KEY] for t in ticks) / len(ticks)
