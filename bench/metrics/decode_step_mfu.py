"""The whole decode step's share of its roofline: the least time of the
window's decode steps (the larger of ops over the int8 peak and bytes over
HBM bandwidth; bytes are the int8 weights once and the K/V rows of the
active lanes at their depths, read and written), over the mean decode
dispatch time the engine measured."""


def read(ctx):
    c = ctx.counters
    if not ctx.decodes or not c.get("decode_dispatches"):
        return None
    least = sum(ctx.costs.least_time(*ctx.costs.decode_step(ctx.arch, lanes, keys), ctx.peak)
                for lanes, keys in ctx.decodes) / len(ctx.decodes)
    return 100.0 * least / (c["decode_time_s"] / c["decode_dispatches"])
