"""The chunk step's share of the int8 peak: operations of the lanes that
were prefilling (a lane parked on scratch counts no work; an overlapping
tail chunk counts its new rows only), over the peak and the mean chunk
dispatch time the engine measured."""


def read(ctx):
    c = ctx.counters
    if not ctx.chunks or not c.get("prefill_dispatches"):
        return None
    s = ctx.serve["seq_len"]
    ops = sum(ctx.costs.chunk_ops(ctx.arch, start, s, first_new=held) for call in ctx.chunks
              for start, held in call) / len(ctx.chunks)
    return 100.0 * ops / ctx.peak["int8_ops_per_s"] / (c["prefill_time_s"] / c["prefill_dispatches"])
