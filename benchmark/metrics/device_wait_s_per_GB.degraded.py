"""device_wait_s_per_GB.degraded: device_wait span seconds (the program
call through block_until_ready, noise_ec_stage_seconds delta) per GB of
degraded stripe reads. None where the program has no such span."""


def read(ctx):
    gb = ctx.gb("read_stripe")
    if "device_wait" not in ctx.delta.stage_s or gb <= 0:
        return None
    return ctx.delta.stage_seconds("device_wait") / gb
