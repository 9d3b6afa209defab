"""codec_roofline.degraded: HBM bytes the window's degraded reads needed
(lib/work.py) at the chip's peak bandwidth, over kernel time in the
device trace."""

from lib import readers


def read(ctx):
    return readers.roofline_pct(ctx, readers.degraded_read_bytes_needed(ctx))
