"""mix_ops_per_s: operations started in the window and completed, over
the window start to the last completion (host clock)."""

from lib import readers


def read(ctx):
    return readers.rate(ctx, sum(1 for op in ctx.ops if op.ok))
