"""Engine admission: mean length of the ``repro.engine.admit`` span
(prefill, the admission argmax, which waits for the device work queued
before it, and the install into the slot)."""

from benchkit.spans import ADMIT, mean_ms, spans


def read(ctx):
    return mean_ms(spans(ctx.rows, ADMIT))
