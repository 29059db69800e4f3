"""Whole-model share of the chip's bf16 peak over the window: required
FLOPs of every bind's prefill (prompt as sent) and decode (each served
token at its live context), over the window times the peak."""

def read(ctx):
    run = ctx.run
    peak = ctx.peaks.get("bf16_flops_per_s")
    if not run.window_s or not peak:
        return None
    lm = ctx.cost("lm_forward")
    flops = 0.0
    for r in run.requests:
        if r["failed"]:
            continue
        n = len(r["prompt"]) + len(r["tokens"]) - 1
        flops += lm.sequence_flops(ctx.model, n)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.window_s * peak)
