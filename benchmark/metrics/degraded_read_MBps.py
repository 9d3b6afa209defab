"""degraded_read_MBps: payload MB of stripe reads started in the window
and answered, over the window start to the last completion (host clock)."""

from lib import readers


def read(ctx):
    return readers.mb_per_s(ctx, "read_stripe")
