"""Share of the traced window the device spends in the Pallas silumul
kernel, SwiGLU's gated product (one call a layer in every chunk and decode
step): the device time of its ``silumul_pallas`` events over the traced
window.  A program that runs the op through XLA names no such kernel, and
gives nothing to read."""

PREFIXES = ("silumul_pallas",)


def read(ctx):
    dev_s, events = ctx.kernel_time(PREFIXES)
    if not events or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * dev_s / ctx.trace["window_s"]
