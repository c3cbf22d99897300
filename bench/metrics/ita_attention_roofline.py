"""The Pallas ita_attention kernel's share of its roofline, from the device
trace: the least time of its calls over the device time of its events.  A
call is one layer's attention over the batch: ops 4.B.H.S^2.D, bytes Q, K
and V in and the output out (int8), each once.  At S=512 and D=64 ops
and bytes need about the same time on a v5e; the larger binds."""

PREFIXES = ("ita_attention_pallas",)


def read(ctx):
    dev_s, events = ctx.kernel_time(PREFIXES)
    if not events or dev_s <= 0:
        return None
    a = ctx.arch
    b, s = ctx.traffic["batch"], ctx.traffic["seq_len"]
    h, d = a["n_heads"], a["head_dim"]
    least = ctx.costs.least_time(ctx.costs.attention_ops(b, h, s, s, d),
                                 ctx.costs.attention_bytes(b, h, s, s, d), ctx.peak)
    return 100.0 * least * len(events) / dev_s
