"""device_op_s_per_GB.degraded: host-timed device dispatch seconds
(noise_ec_device_op_seconds delta) per GB of degraded stripe reads."""

from lib import readers


def read(ctx):
    return readers.device_op_s_per_gb(ctx, "read_stripe")
