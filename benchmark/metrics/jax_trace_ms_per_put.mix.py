"""jax_trace_ms_per_put.mix: jax_trace span milliseconds (JAX tracing a
function, from its jax.monitoring events, noise_ec_stage_seconds delta)
per acknowledged PUT of the mix. None where the program has no such
span."""


def read(ctx):
    puts = len(ctx.ok("put"))
    if "jax_trace" not in ctx.delta.stage_s or not puts:
        return None
    return ctx.delta.stage_seconds("jax_trace") * 1e3 / puts
