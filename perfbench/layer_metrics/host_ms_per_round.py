"""Serving host: the part of each ``OLAWorkloadServer.step`` in which the
device ran nothing, per round (ms)."""


def read(ctx):
    tr = ctx["trace"]
    seconds, steps = tr.span_seconds("ola.step")
    if not steps or not tr.devices:
        return None
    return (seconds - tr.busy_in("ola.step")) / steps * 1e3
