"""1 - (union of device operation intervals / traced span), from the trace."""


def read(ctx):
    red = ctx.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
