"""Pallas TPU kernel: elementwise i-GeLU (ITA activation unit, standalone).

Normally the activation fuses into the GEMM epilogue (``int8_gemm``); this
standalone kernel serves graph positions where the planner could not fuse
(e.g. activation after a residual add).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.igelu import IGeluParams, igelu_int
from repro.quant.qparams import requantize


def _igelu_kernel(x_ref, o_ref, *, gelu: IGeluParams, mult: int, shift: int):
    raw = igelu_int(x_ref[...], gelu)
    o_ref[...] = requantize(raw, mult, shift)


@functools.partial(
    jax.jit, static_argnames=("gelu", "mult", "shift", "block_m", "block_n", "interpret")
)
def igelu_pallas(
    x_q: jnp.ndarray,  # int8 [M, N]
    *,
    gelu: IGeluParams,
    mult: int,
    shift: int,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = x_q.shape
    assert m % block_m == 0 and n % block_n == 0, ((m, n), (block_m, block_n))
    kernel = functools.partial(_igelu_kernel, gelu=gelu, mult=mult, shift=shift)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(x_q)
