"""codec_roofline.put: HBM bytes the window's PUTs needed (lib/work.py) at
the chip's peak bandwidth, over kernel time in the device trace."""

from lib import readers


def read(ctx):
    return readers.roofline_pct(ctx, readers.put_bytes_needed(ctx))
