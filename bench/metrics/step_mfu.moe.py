"""The served work's share of the int8 peak over the window, for a decoder
with expert layers: operations of the prompt tokens newly prefilled (an
overlapping tail chunk counts its new rows only) and of the generated
tokens, each at its depth, with activated work only (attention, router,
the routed experts, the shared expert and its gate), and the head once per
token sampled; over the window and the peak (``bench/costs_moe``).  A run
without expert layers or without probed steps gives nothing to read."""

from bench import costs_moe


def read(ctx):
    if not ctx.arch.get("n_experts") or not (ctx.decodes or ctx.chunks):
        return None
    s = ctx.serve["seq_len"]
    ops = sum(costs_moe.chunk_ops(ctx.arch, start, s, first_new=held) for call in ctx.chunks
              for start, held in call)
    ops += sum(costs_moe.decode_ops(ctx.arch, lanes, keys) for lanes, keys in ctx.decodes)
    ops += ctx.counters.get("tokens_generated", 0) * costs_moe.head_ops(ctx.arch)
    return 100.0 * ops / ctx.window_s / ctx.peak["int8_ops_per_s"]
