"""95th percentile over completed requests of (completion - first
token) / (tokens - 1)."""

from benchkit.readers import percentile


def read(ctx):
    v = percentile([(r["completed_s"] - r["first_token_s"])
                    / (len(r["tokens"]) - 1)
                    for r in ctx.run.requests
                    if not r["failed"] and len(r["tokens"]) > 1], 95)
    return None if v is None else 1e3 * v
