"""entry points: percent of the window under `linearizable.certify`
spans (the host search that certifies a valid device verdict with its
linearization); None where the program records no such span."""

from core.readers import span_share

NAMES = {"linearizable.certify"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
