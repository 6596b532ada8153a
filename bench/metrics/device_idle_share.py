"""Device: the share of the traced window in which no operation ran on a
chip, averaged over the chips (1 - busy / window)."""


def read(ctx):
    if not ctx.busy_s or not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
