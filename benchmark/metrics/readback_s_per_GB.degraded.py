"""readback_s_per_GB.degraded: readback span seconds (the copy of a
ready dispatch output to the host, noise_ec_stage_seconds delta) per GB
of degraded stripe reads. None where the program has no such span."""


def read(ctx):
    gb = ctx.gb("read_stripe")
    if "readback" not in ctx.delta.stage_s or gb <= 0:
        return None
    return ctx.delta.stage_seconds("readback") / gb
