"""crypto_s_per_GB.put: sign + verify span seconds (noise_ec_stage_seconds
deltas) per GB acknowledged by PUTs."""

from lib import readers


def read(ctx):
    return readers.span_s_per_gb(ctx, ("sign", "verify"), "put")
