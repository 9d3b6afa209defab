"""reconstruct_s_per_GB.degraded: reconstruct span seconds (the codec
call inside StripeStore.read, noise_ec_stage_seconds delta) per GB of
degraded stripe reads. None where the program has no such span."""


def read(ctx):
    gb = ctx.gb("read_stripe")
    if "reconstruct" not in ctx.delta.stage_s or gb <= 0:
        return None
    return ctx.delta.stage_seconds("reconstruct") / gb
