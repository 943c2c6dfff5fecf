"""entry points: percent of the window under `linearizable.confirm`
spans (the failing prefix's cut and its host re-check after an invalid
device verdict); None where the program records no such span."""

from core.readers import span_share

NAMES = {"linearizable.confirm"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
