"""1 - (union of the device's operation intervals / traced window)."""


def read(ctx):
    return ctx.idle_share()
