"""kernels: the wide-state level step's share of its HBM roofline, read
as `level_step_roofline` reads it (the least bytes a level moves, from
the `device.level` telemetry and `core/readers.config_bytes`, over the
level-loop programs' device time) by calling that reader.  The queue's
`state_bits` (its model file) is one bit per value a history can
enqueue, the least any implementation of the multiset needs, so packing
the program's lanes tighter moves the share and not the yardstick."""

from core import spec


def read(ctx):
    return spec.reader({"name": "level_step_roofline"})(ctx)
