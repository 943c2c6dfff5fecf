"""entry points: percent of the window under `independent.split` spans
(each test's split into per-key subhistories); None where the program
records no such span."""

from core.readers import span_share

NAMES = {"independent.split"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
