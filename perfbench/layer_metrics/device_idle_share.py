"""Device: share of the window in which no operation ran (%)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.devices or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s / tr.window_s) * 100.0
