"""gate_wait_ms_per_put.mix: gate_wait span milliseconds (contended
DeviceGate admissions, noise_ec_stage_seconds delta) per acknowledged
PUT of the mix. None where the program has no such span."""


def read(ctx):
    puts = len(ctx.ok("put"))
    if "gate_wait" not in ctx.delta.stage_s or not puts:
        return None
    return ctx.delta.stage_seconds("gate_wait") * 1e3 / puts
