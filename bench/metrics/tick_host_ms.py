"""Mean host time of a tick: its stage and its finish less the part of the
finish spent blocked on the device (`StudyGateway.stats[]`: `stage_ms +
finish_ms - wait_ms`), over the window's ticks.  Ticks without those keys
give None."""

KEYS = ("stage_ms", "finish_ms", "wait_ms")


def read(ctx):
    ticks = ctx.ticks
    if not ticks or any(k not in t for t in ticks for k in KEYS):
        return None
    return sum(t["stage_ms"] + t["finish_ms"] - t["wait_ms"]
               for t in ticks) / len(ticks)
