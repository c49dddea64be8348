"""Mean time a serving tick spends splitting the asking studies' PRNG keys,
read-back included (`StudyGateway.stats[].keys_ms`), over the window's
ticks that served asks.  Ticks without the key give None."""

KEY = "keys_ms"


def read(ctx):
    ticks = ctx.ticks
    if not ticks or any(KEY not in t for t in ticks):
        return None
    served = [t[KEY] for t in ticks if t["width"]]
    if not served:
        return None
    return sum(served) / len(served)
