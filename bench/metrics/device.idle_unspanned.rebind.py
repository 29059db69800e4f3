"""Share of the device's idle time that no ``repro.*`` span of the
program overlaps: the gaps between the chip's busy intervals that the
program's spans leave unnamed, in %."""

from benchkit.spans import unspanned_idle_share


def read(ctx):
    return unspanned_idle_share(ctx.rows)
