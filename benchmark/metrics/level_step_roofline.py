"""kernels: the level loop's share of its roofline.  The least bytes any
implementation must move: the encoded history once per slice, and per
level its frontier in and out (telemetry occupancy and next_count, from
the `device.level` spans) at the fewest bytes a configuration can take
(core/readers.config_bytes).  Over the chip's HBM bandwidth, that is
the least time; divided by the level-loop programs' device time.  Bound:
bytes (the search does no arithmetic worth counting)."""

from core import spec
from core.readers import LEVEL_KERNELS, config_bytes, module_seconds, \
    table_bytes


def read(ctx):
    secs = module_seconds(ctx, LEVEL_KERNELS)
    if not secs:
        return None
    t0, t1 = ctx["window"]
    total = 0
    for idx, _res, c0, c1 in ctx["calls"]:
        rows = ctx["pool"][idx]["rows"]
        if len(rows) != 1:
            return None   # per-level spans are the solo route's
        (rows,) = rows.values()
        cfg = config_bytes(rows, ctx["state_bits"])
        for s in ctx["spans"]:
            if c0 <= s["t0"] < c1 and t0 <= s["t0"] < t1:
                if s["name"] == "device.level":
                    total += cfg * (s["args"].get("occupancy", 0)
                                    + s["args"].get("next_count", 0))
                elif s["name"] == "device.slice":
                    total += table_bytes(rows, ctx["state_bits"])
    if not total:
        return None
    peak = spec.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * total / peak / secs
