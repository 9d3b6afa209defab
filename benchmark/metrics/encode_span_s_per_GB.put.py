"""encode_span_s_per_GB.put: encode span seconds (noise_ec_stage_seconds
delta) per GB acknowledged by PUTs."""

from lib import readers


def read(ctx):
    return readers.span_s_per_gb(ctx, ("encode",), "put")
