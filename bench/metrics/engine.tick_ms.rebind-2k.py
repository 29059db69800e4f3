"""Engine decode tick: from the start of each ``repro.engine.decode`` span
(the step's dispatch) to the end of the ``repro.engine.readback`` span
after it (the tick's one device-to-host read, which waits for the step);
mean over the window's ticks."""

from benchkit.spans import DECODE, READBACK, mean_ms, paired


def read(ctx):
    return mean_ms(paired(ctx.rows, DECODE, READBACK, to_end=True))
