"""The Pallas expert_gemm kernel's share of its roofline over the window:
the least time of the expert layers' grouped GEMMs (the larger of their
operations over the int8 peak and their bytes over HBM bandwidth, from the
engine's ``expert_rows`` and ``expert_groups`` counters:
``bench/costs_moe.expert_gemm_cost``), over the device time of the
``expert_gemm_pallas`` events.  A run with no expert counters or no such
kernel gives nothing to read."""

from bench import costs_moe

PREFIXES = ("expert_gemm_pallas",)


def read(ctx):
    rows = ctx.counters.get("expert_rows")
    dev_s, events = ctx.kernel_time(PREFIXES)
    if not rows or not events or dev_s <= 0:
        return None
    ops, nbytes = costs_moe.expert_gemm_cost(ctx.arch, rows, ctx.counters["expert_groups"])
    return 100.0 * costs_moe.least_time(ops, nbytes, ctx.peak) / dev_s
