"""The ``ita`` runner of the plan's ``silumul`` op: any ``[..., F]`` shape
onto the kernel's ``[M, F]`` tiles.

Tiles of 32 x 512 measured fastest on a TPU v5e, both at olmo-1b's chunk
step ([4096, 8192], 4.69 ms a call) and its decode step ([32, 8192],
36.7 us).  Larger blocks hold the elementwise temporaries in VMEM (12 MB
at 128 x 1024, against 86 KB at 32 x 128) and ran 1-15% slower (PERF.md,
block sweep)."""

from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels.interpret import interpret_mode
from repro.kernels.silumul.kernel import silumul_pallas
from repro.models import layers as L

#: int8 sublane tile: rows are padded to a multiple of it, one tile a block
ROW_TILE = 32


def silumul(g_q: jnp.ndarray, u_q: jnp.ndarray, *, scales, live_rows=None) -> jnp.ndarray:
    """``live_rows`` (int32 scalar): rows from it on are zero in the result,
    and their blocks are neither read nor computed."""
    *lead, f = g_q.shape
    m = math.prod(lead)
    pad = -m % ROW_TILE
    g2, u2 = g_q.reshape(m, f), u_q.reshape(m, f)
    if pad:
        # zero rows, sliced away after the kernel
        g2 = jnp.pad(g2, ((0, pad), (0, 0)))
        u2 = jnp.pad(u2, ((0, pad), (0, 0)))
    live = None
    if live_rows is not None:
        live = jnp.reshape((jnp.asarray(live_rows, jnp.int32) + ROW_TILE - 1) // ROW_TILE, (1,))
    out = silumul_pallas(
        g2, u2, params=L.silumul_params(*scales),
        block_m=ROW_TILE,
        block_n=next((b for b in (512, 128) if f % b == 0), f),
        live=live,
        interpret=interpret_mode(),
    )
    if pad:
        out = out[:m]
    return out.reshape(*lead, f)
