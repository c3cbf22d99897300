"""Pallas TPU kernel: standalone rowwise ITAMax (paper-faithful two-pass).

Used when the softmax is *not* fused into an attention product — e.g. the
MoE router, or the paper-faithful ITA schedule where 8-bit ``A`` is
materialized before the ``A V`` matmul (rows <= 512 in the ASIC; here the
row must fit a VMEM block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import itamax as im


def _itamax_kernel(x_ref, o_ref):
    o_ref[...] = im.itamax_rowwise(x_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def itamax_pallas(
    logits: jnp.ndarray,  # int8 [R, n] — full row per block
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    r, n = logits.shape
    assert r % block_rows == 0, (r, block_rows)
    return pl.pallas_call(
        _itamax_kernel,
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(logits)
