"""Pallas TPU kernel: the dropless grouped int8 GEMM of an expert layer.

Rows arrive sorted by expert, each expert's group padded to the row tile
(``repro.models.moe.dispatch``).  A scalar-prefetched map gives each row
tile its expert; the weight block's ``index_map`` reads it, so one grid
step multiplies one tile of rows by its own expert's ``[K, block_n]``
weights.  The grid is static (the buffer's tiles); tiles past the last live
one repeat its row and weight block indices, so the pipeline fetches
nothing for them, and write zeros.  An expert with no rows has no tile and
costs no weight read.  Consecutive tiles of one expert keep its weight
block resident: the tile axis is the inner one and ``K`` is one block.

The epilogue is ``int8_gemm``'s (``kernels/int8_gemm/kernel.epilogue``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_gemm.kernel import epilogue


def _kernel(tile_e_ref, live_ref, x_ref, w_ref, mult_ref, shift_ref, o_ref):
    live = pl.program_id(1) < live_ref[0]

    @pl.when(live)
    def _compute():
        acc = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.int32)
        o_ref[...] = epilogue(acc, mult_ref[...], shift_ref[...])

    @pl.when(jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def expert_gemm_pallas(
    x_q: jnp.ndarray,  # int8 [P, K], groups sorted by expert
    tile_expert: jnp.ndarray,  # int32 [P // block_m]
    live: jnp.ndarray,  # int32 [1]: live tiles
    w_q: jnp.ndarray,  # int8 [E, K, N]
    mult: jnp.ndarray,  # int32 [N]
    shift: jnp.ndarray,  # int32 [N]
    *,
    block_m: int,
    block_n: int,
    interpret: bool = False,
) -> jnp.ndarray:
    p, k = x_q.shape
    _, _, n = w_q.shape
    assert p % block_m == 0 and n % block_n == 0, ((p, n), (block_m, block_n))
    tiles = p // block_m

    def row_block(j, i, tile_e, live):
        return (jnp.minimum(i, jnp.maximum(live[0] - 1, 0)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block_n, tiles),
        in_specs=[
            pl.BlockSpec((block_m, k), row_block),
            pl.BlockSpec((1, k, block_n), lambda j, i, tile_e, live: (tile_e[i], 0, j)),
            pl.BlockSpec((1, block_n), lambda j, i, tile_e, live: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, i, tile_e, live: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda j, i, tile_e, live: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tile_expert, live, x_q, w_q, mult[None, :], shift[None, :])
