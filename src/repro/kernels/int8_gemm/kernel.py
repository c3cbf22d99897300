"""Pallas TPU kernel: int8 GEMM + bias + fixed-point requant + activation.

This is ITA's GEMM mode mapped onto the MXU: int8 x int8 -> int32
accumulation in VMEM scratch across the K grid dimension, with the
requantization (+ optional ReLU / i-GeLU) epilogue fused into the last K
step — the TPU analogue of ITA's output-stationary dataflow with the
activation unit on the output path.

Block shapes are chosen by the deploy planner subject to the VMEM budget
(the TPU analogue of Deeploy's L1 tiling constraints); the MXU wants the
last two dims in multiples of (8, 128) at int8 (we use 128-aligned tiles,
see ``repro.core.heterogeneous.TPU_GRANULE``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.igelu import IGeluParams, igelu_int
from repro.core.quant_linear import ACT_GELU, ACT_IDENTITY, ACT_RELU
from repro.quant.qparams import requantize


def _gemm_kernel(
    x_ref,  # (bm, bk) int8
    w_ref,  # (bk, bn) int8
    bias_ref,  # (1, bn) int32
    mult_ref,  # (1, bn) int32   per-channel requant multiplier
    shift_ref,  # (1, bn) int32
    o_ref,  # (bm, bn) int8
    acc_ref,  # VMEM scratch (bm, bn) int32
    *,
    act: int,
    gelu: IGeluParams | None,
    gelu_mult: int,
    gelu_shift: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.int8),
        w_ref[...].astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...] + bias_ref[...]
        mult = mult_ref[...]
        shift = shift_ref[...]
        if act == ACT_IDENTITY:
            o_ref[...] = requantize(acc, mult, shift)
        elif act == ACT_RELU:
            o_ref[...] = requantize(jnp.maximum(acc, 0), mult, shift)
        elif act == ACT_GELU:
            pre = requantize(acc, mult, shift)
            raw = igelu_int(pre, gelu)
            o_ref[...] = requantize(raw, gelu_mult, gelu_shift)
        else:
            raise ValueError(f"unknown act {act}")


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_n",
        "block_k",
        "act",
        "gelu",
        "gelu_mult",
        "gelu_shift",
        "interpret",
    ),
)
def int8_gemm_pallas(
    x_q: jnp.ndarray,  # int8 [M, K]
    w_q: jnp.ndarray,  # int8 [K, N]
    bias_q: jnp.ndarray,  # int32 [N]
    mult: jnp.ndarray,  # int32 [N] (broadcast per-tensor upstream)
    shift: jnp.ndarray,  # int32 [N]
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    act: int = ACT_IDENTITY,
    gelu: IGeluParams | None = None,
    gelu_mult: int = 0,
    gelu_shift: int = 31,
    interpret: bool = False,
) -> jnp.ndarray:
    m, kdim = x_q.shape
    _, n = w_q.shape
    assert kdim % block_k == 0 and m % block_m == 0 and n % block_n == 0, (
        (m, kdim, n),
        (block_m, block_k, block_n),
    )
    grid = (m // block_m, n // block_n, kdim // block_k)
    kernel = functools.partial(
        _gemm_kernel,
        act=act,
        gelu=gelu,
        gelu_mult=gelu_mult,
        gelu_shift=gelu_shift,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, w_q, bias_q[None, :], mult[None, :], shift[None, :])
