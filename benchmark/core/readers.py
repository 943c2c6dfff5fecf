"""What the per-layer readers share: the window, the program's spans
clipped to it, and the device time of named programs in the trace."""

from __future__ import annotations

import math
import re

from .xtrace import MODULES_LINE, union


def window_s(ctx):
    t0, t1 = ctx["window"]
    return t1 - t0


def clipped(ctx, names=None):
    """[start, end) of the program's spans (those named, or all) within
    the window."""
    t0, t1 = ctx["window"]
    return [(max(t0, s["t0"]), min(t1, s["t1"])) for s in ctx["spans"]
            if (names is None or s["name"] in names)
            and s["t1"] > t0 and s["t0"] < t1]


def span_share(ctx, names):
    """Percent of the window covered by the named spans; None where the
    program recorded no span at all (tracing did not reach it)."""
    if not ctx["spans"]:
        return None
    return 100.0 * union(clipped(ctx, names)) / window_s(ctx)


def module_seconds(ctx, pattern):
    """Device seconds of the program executions whose names match
    ``pattern``, in the window, averaged over the device planes."""
    t0, t1 = ctx["window"]
    rx = re.compile(pattern)
    by = {}
    for e in ctx["events"]:
        if e["line"] == MODULES_LINE and rx.search(e["name"]) \
                and t0 <= e["t0"] < t1:
            by[e["plane"]] = by.get(e["plane"], 0.0) + e["dur"]
    return sum(by.values()) / len(by) if by else 0.0


#: the programs of the BFS level loop: every route jits a function named
#: `step` (the XLA solo step, its vmapped batch form, the Pallas fused
#: level loop), which the trace names `jit_step(<hash>)`
LEVEL_KERNELS = r"^jit_step\("


def config_bytes(rows, state_bits):
    """The fewest bytes one search configuration of this history can
    take: its place in the history, one bit per op in the widest window
    of ops open at once, one per crashed op, and the model's state
    (``state_bits``, from the model's file)."""
    events = []
    for op in rows:
        if op["type"] == "ok":
            events += [(op["inv"], 1), (op["ret"], -1)]
    width = cur = 0
    for _t, d in sorted(events):
        cur += d
        width = max(width, cur)
    crashed = sum(1 for op in rows if op["type"] != "ok")
    bits = (math.ceil(math.log2(len(rows) + 1)) + width + crashed
            + state_bits)
    return math.ceil(bits / 8)


def table_bytes(rows, state_bits):
    """The fewest bytes of the encoded history a search has to read once:
    per op its function (2 bits), two values of ``state_bits`` each and
    two event positions."""
    n_events = 2 * len(rows)
    bits = 2 + 2 * state_bits + 2 * math.ceil(math.log2(n_events + 1))
    return math.ceil(len(rows) * bits / 8)
