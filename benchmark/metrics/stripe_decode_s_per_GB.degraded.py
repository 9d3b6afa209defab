"""stripe_decode_s_per_GB.degraded: stripe_decode span seconds
(noise_ec_stage_seconds delta) per GB of degraded stripe reads."""

from lib import readers


def read(ctx):
    return readers.span_s_per_gb(ctx, ("stripe_decode",), "read_stripe")
