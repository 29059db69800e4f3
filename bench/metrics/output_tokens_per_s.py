"""Output tokens produced in the window (as the server reported them on
its per-tick lease renewals and completions), over the window."""


def read(ctx):
    run = ctx.run
    return run.window_tokens / run.window_s if run.window_s else None
