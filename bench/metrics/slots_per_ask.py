"""Slots the fused advance computed per ask it served: every tick that
serves asks runs the EI ascent for all `slots` lanes of the stacked state,
whatever the number of asks (`stats[].width`)."""


def read(ctx):
    served = [t["width"] for t in ctx.ticks if t["width"]]
    if not served:
        return None
    return ctx.slots * len(served) / sum(served)
