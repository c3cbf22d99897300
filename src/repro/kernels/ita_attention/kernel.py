"""Pallas TPU kernel: fused int8 attention with streaming ITAMax softmax.

The paper's core dataflow — ``Q K^T`` streaming through the ITAMax unit
(denominator accumulation with running-max renormalization) with the
``A V`` product fused behind it — mapped onto TPU as a flash-attention-
style kernel:

  grid = (B * H, Sq / bq, Sk / bk), KV innermost ("arbitrary")
  VMEM carry: running max m (bq,1), denominator d (bq,1), un-normalized
  output accumulator acc (bq, D) — ITA's DA stage state, kept per Q tile.
  Last KV step: DI (one exact integer division per row) + EN + requant.

Differences vs the ASIC (documented in DESIGN.md): the ASIC buffers whole
<=512-long rows of int8 logits and normalizes in a second pass; a 32k-500k
row cannot be buffered, so the TPU kernel renormalizes the ``A V``
accumulator on max updates (the flash adaptation) with ITA's shift/LUT
arithmetic.  The computation is bit-exact vs
``repro.core.attention.attention_flash_i8`` at equal KV block size.

GQA is handled in the index map (KV head = Q head // group); the logit
requantization (folding s_q * s_k / sqrt(d) onto the ITAMax grid) runs
inside the kernel on the int32 ``Q K^T`` block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import itamax as im
from repro.quant.qparams import requantize


def _attn_kernel(
    q_ref,  # (1, bq, D) int8
    k_ref,  # (1, bk, D) int8
    v_ref,  # (1, bk, D) int8
    o_ref,  # (1, bq, D) int8
    m_ref,  # VMEM (bq, 1) int32
    d_ref,  # VMEM (bq, 1) int32
    acc_ref,  # VMEM (bq, D) int32
    *,
    logit_mult: int,
    logit_shift: int,
    out_mult: int,
    out_shift: int,
    causal: bool,
    q_offset: int,
    block_q: int,
    block_k: int,
    kv_valid: int,  # true KV length (< Sk when the caller padded)
):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, im.M_SENTINEL)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(1)
    # A KV block is live unless it is entirely above the causal diagonal.
    live = True
    if causal:
        first_q_global = qi * block_q + q_offset
        first_k_global = kstep * block_k
        live = first_k_global <= first_q_global + block_q - 1

    @pl.when(live)
    def _update():
        qb = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            qb,
            kb,
            (((1,), (1,)), ((), ())),  # q @ k.T
            preferred_element_type=jnp.int32,
        )
        logits = requantize(s, logit_mult, logit_shift)
        mask = None
        need_len_mask = kv_valid < pl.num_programs(2) * block_k
        if causal or need_len_mask:
            kg = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + (
                kstep * block_k
            )
            mask = kg < kv_valid
            if causal:
                qg = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + (
                    qi * block_q + q_offset
                )
                mask = mask & (kg <= qg)
        state = im.FlashItamaxState(m=m_ref[...], d=d_ref[...], acc=acc_ref[...])
        new_state = im.flash_block_update(state, logits, v_ref[0], mask)
        m_ref[...] = new_state.m
        d_ref[...] = new_state.d
        acc_ref[...] = new_state.acc

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _finalize():
        state = im.FlashItamaxState(m=m_ref[...], d=d_ref[...], acc=acc_ref[...])
        q77 = im.flash_finalize_q77(state)
        o_ref[0] = requantize(q77, out_mult, out_shift)


@functools.partial(
    jax.jit,
    static_argnames=(
        "group",
        "logit_mult",
        "logit_shift",
        "out_mult",
        "out_shift",
        "causal",
        "block_q",
        "block_k",
        "kv_valid",
        "interpret",
    ),
)
def ita_attention_pallas(
    q_q: jnp.ndarray,  # int8 [BH, Sq, D]   (B and H fused)
    k_q: jnp.ndarray,  # int8 [BHkv, Sk, D]
    v_q: jnp.ndarray,  # int8 [BHkv, Sk, D]
    *,
    group: int,  # H // Hkv (per batch) — q head bh maps to kv head bh//group
    logit_mult: int,
    logit_shift: int,
    out_mult: int,
    out_shift: int,
    causal: bool = False,
    block_q: int = 256,
    block_k: int = 512,
    kv_valid: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, d = q_q.shape
    _, sk, _ = k_q.shape
    assert sq % block_q == 0 and sk % block_k == 0, ((sq, sk), (block_q, block_k))
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _attn_kernel,
        logit_mult=logit_mult,
        logit_shift=logit_shift,
        out_mult=out_mult,
        out_shift=out_shift,
        causal=causal,
        q_offset=sk - sq,
        block_q=block_q,
        block_k=block_k,
        kv_valid=sk if kv_valid is None else kv_valid,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, k: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, k, g=group: (h // g, k, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, k, g=group: (h // g, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, k: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.int32),
            pltpu.VMEM((block_q, d), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_q, k_q, v_q)
