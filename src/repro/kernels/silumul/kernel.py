"""Pallas TPU kernel: SwiGLU's gated product ``requant(isilu(g) * u)``.

The integer SiLU is a cluster op (ITA's activation unit has no SiLU mode);
as an XLA op it reads its 32-entry exp2 table through a gather over every
element of the MLP activation.  Here the same arithmetic runs elementwise
in VMEM, with the table read by the select tree of ``lut_lookup``.

Given ``live`` (the row blocks that carry rows, as scalar prefetch), the
blocks past it repeat the last live block's index, so the pipeline fetches
nothing for them, and write zeros: an expert layer's grouped buffer is
static and mostly padding when few rows are routed.
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


def _silumul_live_kernel(live_ref, g_ref, u_ref, o_ref, *, params: tuple[int, ...]):
    live = pl.program_id(0) < live_ref[0]

    @pl.when(live)
    def _compute():
        _silumul_kernel(g_ref, u_ref, o_ref, params=params)

    @pl.when(jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


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
    live: jnp.ndarray | None = None,  # int32 [1]: row blocks that carry rows
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = g_q.shape
    assert u_q.shape == g_q.shape, (g_q.shape, u_q.shape)
    assert m % block_m == 0 and n % block_n == 0, ((m, n), (block_m, block_n))
    if live is not None:
        def in_block(i, j, live):
            return (jnp.minimum(i, jnp.maximum(live[0] - 1, 0)), j)

        in_spec = pl.BlockSpec((block_m, block_n), in_block)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // block_m, n // block_n),
            in_specs=[in_spec, in_spec],
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, live: (i, j)),
        )
        return pl.pallas_call(
            functools.partial(_silumul_live_kernel, params=params),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=interpret,
        )(live, g_q, u_q)
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
