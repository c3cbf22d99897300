"""The Pallas paged-decode kernel's share of its roofline over the window:
one call is one layer's attention in one decode step, over the active
lanes' key rows.  Its least time is the larger of its operations over the
int8 peak and its bytes over HBM bandwidth: ops 4.H.D.key_rows (QK^T and
A.V), bytes the int8 K and V rows once (2.Hkv.D.key_rows) and each lane's
query in and output out (2.lanes.H.D).  The mean least time over the
window's decode steps, times the ``paged_attention_pallas`` events, over
their device time.  A program that runs decode attention through XLA
names no such kernel, and gives nothing to read; the event count says
how often the kernel engaged."""

PREFIXES = ("paged_attention_pallas",)


def read(ctx):
    dev_s, events = ctx.kernel_time(PREFIXES)
    if not events or dev_s <= 0 or not ctx.decodes:
        return None
    a = ctx.arch
    h, hkv, d = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    least = sum(ctx.costs.least_time(4.0 * h * d * keys,
                                     2.0 * hkv * d * keys + 2.0 * lanes * h * d, ctx.peak)
                for lanes, keys in ctx.decodes) / len(ctx.decodes)
    return 100.0 * least * len(events) / dev_s
