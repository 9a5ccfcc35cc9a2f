"""Round step: device-busy time (union of operation intervals) per round
(ms)."""


def read(ctx):
    if not ctx["rounds"] or not ctx["trace"].devices:
        return None
    return ctx["trace"].busy_s / ctx["rounds"] * 1e3
