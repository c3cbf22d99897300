"""The XLA runner of the grouped int8 GEMM (the op on ``w8a8``, and the
kernel's oracle): ``ragged_dot`` over the padded expert groups, then
``int8_gemm``'s requantisation.  Rows past the live tiles are zero (the
TPU's ``ragged_dot`` leaves rows past its groups unwritten)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant_linear as ql
from repro.quant.qparams import requantize


def group_sizes(tiles: jnp.ndarray, n_experts: int, row_tile: int) -> jnp.ndarray:
    """Rows of each expert's padded group, from the dispatch's ``tiles``
    (the expert of each tile, then the live tiles)."""
    t = tiles.shape[0] - 3
    live = jnp.arange(t, dtype=jnp.int32) < tiles[t]
    return jnp.zeros((n_experts,), jnp.int32).at[tiles[:t]].add(jnp.where(live, row_tile, 0))


def expert_gemm_ref(x_q, tiles, w_q, *, scales, row_tile: int):
    """int8 [P, K] rows grouped by expert . int8 [E, K, N] -> int8 [P, N]."""
    s_in, s_w, s_out = scales
    acc = jax.lax.ragged_dot(x_q, w_q, group_sizes(tiles, w_q.shape[0], row_tile),
                             preferred_element_type=jnp.int32)
    p = ql.make_qlinear_params(s_in, s_w, s_out)
    t = tiles.shape[0] - 3
    live = jnp.arange(x_q.shape[0], dtype=jnp.int32) < tiles[t] * row_tile
    return jnp.where(live[:, None], requantize(acc, p.mult, p.shift), jnp.int8(0))
