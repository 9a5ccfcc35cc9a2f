"""Bi-level sampling and estimation: raw tuples the shared scan extracted
in the window (the ``server_tuples_scanned`` gauge, summed over passes) per
answer."""


def read(ctx):
    if not ctx["answers"]:
        return None
    return ctx["tuples_scanned"] / ctx["answers"]
