"""The served work's share of the int8 peak over the window: operations of
the prompt tokens newly prefilled (an overlapping tail chunk counts its new
rows only) and of the generated tokens, each at its depth, over the window
and the peak."""


def read(ctx):
    if not ctx.decodes and not ctx.chunks:
        return None
    s = ctx.serve["seq_len"]
    ops = sum(ctx.costs.chunk_ops(ctx.arch, start, s, first_new=held) for call in ctx.chunks
              for start, held in call)
    ops += sum(ctx.costs.decode_step(ctx.arch, lanes, keys)[0] for lanes, keys in ctx.decodes)
    return 100.0 * ops / ctx.window_s / ctx.peak["int8_ops_per_s"]
