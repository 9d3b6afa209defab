"""dispatches_per_put.mix: device dispatches (noise_ec_device_op_seconds
count delta) per acknowledged PUT of the mix."""


def read(ctx):
    puts = len(ctx.ok("put"))
    if not puts or not ctx.delta.device_op_n:
        return None
    return ctx.delta.device_op_n / puts
