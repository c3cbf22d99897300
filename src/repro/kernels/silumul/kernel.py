"""Pallas TPU kernel: SwiGLU's gated product ``requant(isilu(g) * u)``.

The integer SiLU is a cluster op (ITA's activation unit has no SiLU mode);
as an XLA op it reads its 32-entry exp2 table through a gather over every
element of the MLP activation.  Here the same arithmetic runs elementwise
in VMEM, with the table read by the select tree of ``lut_lookup``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import itamax as im
from repro.models import layers as L


def _silumul_kernel(g_ref, u_ref, o_ref, *, params: tuple[int, ...]):
    o_ref[...] = L.silumul_int(g_ref[...], u_ref[...], params, lookup=im.lut_lookup)


@functools.partial(
    jax.jit, static_argnames=("params", "block_m", "block_n", "interpret")
)
def silumul_pallas(
    g_q: jnp.ndarray,  # int8 [M, F] gate codes
    u_q: jnp.ndarray,  # int8 [M, F] up codes
    *,
    params: tuple[int, ...],  # L.silumul_params(s_g, s_u, s_out)
    block_m: int,
    block_n: int,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = g_q.shape
    assert u_q.shape == g_q.shape, (g_q.shape, u_q.shape)
    assert m % block_m == 0 and n % block_n == 0, ((m, n), (block_m, block_n))
    spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_silumul_kernel, params=params),
        grid=(m // block_m, n // block_n),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(g_q, u_q)
