"""Nearest-rank p95 of the gaps between consecutive tokens of a request,
whose later token falls in the window (host clock).  A gap is one engine
step: a decode step, or a decode step behind the chunk steps the scheduler
ran first, so above the knee its tail is the chunk steps' stall.  It is
read here and not held to a bound."""


def read(ctx):
    return ctx.e2e.get("itl_p95_ms")
