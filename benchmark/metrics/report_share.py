"""entry points: percent of the window under `linearizable.shrink` (the
failing history delta-debugged) and `linearizable.report` (linear.html
written) spans; None where the program records neither."""

from core.readers import span_share

NAMES = {"linearizable.shrink", "linearizable.report"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
