"""crypto_s_per_GB.mix: sign + verify span seconds (noise_ec_stage_seconds
deltas) per GB acknowledged by the mix's PUTs."""

from lib import readers


def read(ctx):
    return readers.span_s_per_gb(ctx, ("sign", "verify"), "put")
