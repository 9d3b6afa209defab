"""store_lock_wait_ms_per_op.mix: store_lock_wait span milliseconds
(contended StripeStore lock acquisitions, noise_ec_stage_seconds delta)
per completed operation of the mix. None where the program has no such
span."""


def read(ctx):
    ops = sum(1 for op in ctx.ops if op.ok)
    if "store_lock_wait" not in ctx.delta.stage_s or not ops:
        return None
    return ctx.delta.stage_seconds("store_lock_wait") * 1e3 / ops
