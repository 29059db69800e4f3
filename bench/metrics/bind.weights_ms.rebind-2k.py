"""Weights from the seed in each bind: mean length of the serve payload's
``repro.payload.weights`` span (``make_inputs``)."""

from benchkit.spans import WEIGHTS, mean_ms, spans


def read(ctx):
    return mean_ms(spans(ctx.rows, WEIGHTS))
