"""coalesce_wait_ms_per_put.mix: coalesce_wait span milliseconds (leader
lingers and follower waits in the coalescer, noise_ec_stage_seconds
delta) per acknowledged PUT of the mix. None where the program has no
such span."""


def read(ctx):
    puts = len(ctx.ok("put"))
    if "coalesce_wait" not in ctx.delta.stage_s or not puts:
        return None
    return ctx.delta.stage_seconds("coalesce_wait") * 1e3 / puts
