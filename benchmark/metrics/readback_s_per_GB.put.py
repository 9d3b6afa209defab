"""readback_s_per_GB.put: readback span seconds (the copy of a ready
dispatch output to the host, noise_ec_stage_seconds delta) per GB
acknowledged by PUTs. None where the program has no such span."""


def read(ctx):
    gb = ctx.gb("put")
    if "readback" not in ctx.delta.stage_s or gb <= 0:
        return None
    return ctx.delta.stage_seconds("readback") / gb
