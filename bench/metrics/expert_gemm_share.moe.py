"""Share of the traced window the device spends in the Pallas expert_gemm
kernel (three calls a layer in every chunk and decode step): the device
time of its ``expert_gemm_pallas`` events over the traced window.  A
program with no expert layer, or one that runs the op through XLA, names no
such kernel and gives nothing to read."""

PREFIXES = ("expert_gemm_pallas",)


def read(ctx):
    dev_s, events = ctx.kernel_time(PREFIXES)
    if not events or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * dev_s / ctx.trace["window_s"]
