"""put_MBps: payload MB of acknowledged PUTs started in the window, over
the window start to the last completion (host clock)."""

from lib import readers


def read(ctx):
    return readers.mb_per_s(ctx, "put")
