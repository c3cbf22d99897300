"""Pure-jnp oracle for the silumul kernel: the XLA runner of the plan's
``silumul`` op, which reads the exp2 table with an XLA gather."""

import math

import jax.numpy as jnp

from repro.models import layers as L


def silumul_ref(g_q, u_q, *, scales, live_rows=None):
    """``live_rows``: rows of the ``[M, F]`` view from it on are zero."""
    out = L.silumul_int(g_q, u_q, L.silumul_params(*scales))
    if live_rows is None:
        return out
    *lead, _ = out.shape
    rows = jnp.arange(math.prod(lead), dtype=jnp.int32).reshape(*lead, 1)
    return jnp.where(rows < live_rows, out, jnp.int8(0))
