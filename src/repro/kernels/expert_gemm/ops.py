"""The ``ita`` runner of the plan's grouped expert GEMM: scale plumbing and
block choice around ``expert_gemm_pallas``.

The row block is the dispatch's row tile (each expert's group is padded
to it); the column block is the whole output width where it fits VMEM's
double-buffered weight blocks, so each expert's weights are read once a
call (PERF.md, expert_gemm block sweep)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.expert_gemm.kernel import expert_gemm_pallas
from repro.kernels.interpret import interpret_mode
from repro.quant.qparams import np_quantize_multiplier

#: bytes of one weight block [K, block_n] allowed in VMEM (double-buffered)
WEIGHT_BLOCK_BYTES = 4 << 20


def block_n_for(k: int, n: int) -> int:
    """The widest column block dividing ``n`` whose int8 ``[k, block_n]``
    weight block stays within :data:`WEIGHT_BLOCK_BYTES`."""
    fits = [b for b in range(128, n + 1, 128) if n % b == 0 and k * b <= WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else n


def expert_gemm(x_q, tiles, w_q, *, scales, row_tile: int):
    """int8 [P, K] rows grouped by expert . int8 [E, K, N] -> int8 [P, N];
    ``tiles`` is the dispatch's tile map (``repro.models.moe.dispatch``)."""
    s_in, s_w, s_out = scales
    p, k = x_q.shape
    n = w_q.shape[-1]
    t = p // row_tile
    mult_np, shift_np = np_quantize_multiplier(np.full((n,), s_in * s_w / s_out))
    return expert_gemm_pallas(
        x_q, tiles[:t], tiles[t:t + 1], w_q,
        jnp.asarray(mult_np, jnp.int32), jnp.asarray(shift_np, jnp.int32),
        block_m=row_tile, block_n=block_n_for(k, n),
        interpret=interpret_mode(),
    )
