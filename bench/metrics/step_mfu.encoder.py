"""Sequences per second times the operations of one sequence, over the int8
peak."""


def read(ctx):
    rate = ctx.e2e.get("encoder_seq_per_s")
    if not rate:
        return None
    ops = ctx.costs.encoder_seq_ops(ctx.arch, ctx.traffic["seq_len"])
    return 100.0 * rate * ops / ctx.peak["int8_ops_per_s"]
