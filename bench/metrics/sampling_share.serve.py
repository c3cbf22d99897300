"""Share of the traced window the host spent in the engine's sampling
policy: the union of the trace's ``sampling`` spans (the engine opens one
around each call of the policy; nested duplicates count once) over the
traced window, the window ``device_idle_share.serve`` is a share of.  A
trace with no device, or no ``sampling`` span, gives nothing to read."""

from bench import tracefile


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    spans = [(s, d) for name, s, d in ctx.trace["record"]["host"] if name == "sampling"]
    if not spans:
        return None
    inside_ns = sum(e - s for s, e in tracefile.union_ns(spans))
    return 100.0 * inside_ns / 1e9 / ctx.trace["window_s"]
