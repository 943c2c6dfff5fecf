"""entry points: percent of the window under `independent.key` spans:
the per-key checks outside the batch, of keys too small for the device
and of keys the batch found invalid (device search, host confirmation,
shrink, report); None where the program records no such span."""

from core.readers import span_share

NAMES = {"independent.key"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
