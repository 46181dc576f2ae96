"""Device: the share of the traced window in which no operation ran on
the chip (averaged over the traced chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
