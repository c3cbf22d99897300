"""Pallas TPU kernel: one decode query per lane against the paged int8 pool.

The XLA runner of ``attn_paged`` gathers every lane's whole block table
(``max_len`` rows), widens it to int32 and masks the rows past the lane's
length.  Here each lane reads only its live blocks: the block table and
the lane lengths are scalar-prefetched, the pools stay in HBM, and one DMA
per block copies its ``[Hkv, BS, D]`` rows (contiguous, every head) into
VMEM buffers laid out ``[Hkv, rows, D]``.

The key split is the XLA runner's: one flash-ITAMax state update per
``block_k`` key block.  A block's update runs in two passes over the
block's fetched chunks of ``chunk`` rows:

1. int8 ``Q K^T`` with int32 accumulation, requantized onto the ITAMax
   grid and masked past the lane's length, into a VMEM logits row, with
   the row max;
2. the exponentials against the rescaled running max, their sum and the
   int8 ``val @ V`` product, summed over the chunks.

Integer sums are exact, so this equals one ``flash_block_update`` over the
whole block: rows past the lane's length are masked whatever they hold (a
chunk's stale table entries are fetched, chunks past the length are not),
so they count as the XLA runner's masked rows do, not at all; key blocks
wholly past it are skipped, which is bit-neutral.  Query head ``h`` reads
kv head ``h // (H / Hkv)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import itamax as im
from repro.quant.qparams import requantize


def _paged_kernel(
    lens_ref,  # SMEM int32 [B]: each lane's key rows (pos + 1)
    table_ref,  # SMEM int32 [B * nb]: block tables, row-major
    q_ref,  # (1, H, D) int8
    k_hbm,  # int8 [P+1, Hkv, BS, D], in HBM
    v_hbm,
    o_ref,  # (1, H, D) int8
    k_buf,  # VMEM int8 [Hkv, block_k, D]
    v_buf,
    logits_ref,  # VMEM int32 [H, block_k]
    sems,  # DMA [2, block_k // chunk]
    *,
    logit_mult: int,
    logit_shift: int,
    out_mult: int,
    out_shift: int,
    block_k: int,
    chunk: int,
):
    b = pl.program_id(0)
    hkv, _, d = k_buf.shape
    h = q_ref.shape[1]
    g = h // hkv
    bs = k_hbm.shape[2]
    n_pool = k_hbm.shape[0]
    nb = table_ref.shape[0] // pl.num_programs(0)
    pages = chunk // bs  # blocks of the pool per chunk
    kv_len = lens_ref[b]
    q = q_ref[0].reshape(hkv, g, d)

    def copy(pool, buf, sem, j, c, i):
        """The DMA of block ``i`` of chunk ``c`` of key block ``j``."""
        slot = c * pages + i
        page = jnp.clip(table_ref[b * nb + j * (block_k // bs) + slot], 0, n_pool - 1)
        return pltpu.make_async_copy(pool.at[page], buf.at[:, pl.ds(slot * bs, bs), :],
                                     sems.at[sem, c])

    def k_copy(j, c, i):
        return copy(k_hbm, k_buf, 0, j, c, i)

    def v_copy(j, c, i):
        return copy(v_hbm, v_buf, 1, j, c, i)

    def chunk_rows(j, c):
        start = pl.multiple_of(c * chunk, chunk)
        live = (j * block_k + start
                + jax.lax.broadcasted_iota(jnp.int32, (h, chunk), 1)) < kv_len
        return pl.ds(start, chunk), live

    def key_block(j, state):
        n_chunks = pl.cdiv(jnp.minimum(kv_len - j * block_k, block_k), chunk)

        # all of the block's live chunks in flight, K first: pass 1 waits
        # for K chunk by chunk while V arrives for pass 2
        for make in (k_copy, v_copy):
            @pl.loop(0, n_chunks)
            def _start(c):
                for i in range(pages):
                    make(j, c, i).start()

        def logits_pass(c, block_max):
            for i in range(pages):
                k_copy(j, c, i).wait()
            rows, live = chunk_rows(j, c)
            s = jnp.einsum("hgd,hkd->hgk", q, k_buf[:, rows, :],
                           preferred_element_type=jnp.int32).reshape(h, chunk)
            x = im.flash_mask_logits(requantize(s, logit_mult, logit_shift), live)
            logits_ref[:, rows] = x
            return jnp.maximum(block_max, jnp.max(x, axis=-1, keepdims=True))

        # every masked logit is -128, so -128 starts the row max exactly
        block_max = jax.lax.fori_loop(0, n_chunks, logits_pass,
                                      jnp.full((h, 1), -128, jnp.int32))
        new_m, d_r, acc_r = im.flash_rescale(state, block_max)

        def value_pass(c, sums):
            d_sum, acc_sum = sums
            for i in range(pages):
                v_copy(j, c, i).wait()
            rows, live = chunk_rows(j, c)
            val = im.flash_exp(new_m, logits_ref[:, rows], live)
            # val <= 127: the int8 operand the MXU takes is exact
            pv = jnp.einsum("hgk,hkd->hgd", val.astype(jnp.int8).reshape(hkv, g, chunk),
                            v_buf[:, rows, :], preferred_element_type=jnp.int32)
            return (d_sum + jnp.sum(val, axis=-1, keepdims=True),
                    acc_sum + pv.reshape(h, d))

        d_sum, acc_sum = jax.lax.fori_loop(
            0, n_chunks, value_pass,
            (jnp.zeros((h, 1), jnp.int32), jnp.zeros((h, d), jnp.int32)))
        d_out, acc_out = im.flash_guard(d_r + d_sum, acc_r + acc_sum)
        return im.FlashItamaxState(m=new_m, d=d_out, acc=acc_out)

    state = jax.lax.fori_loop(0, pl.cdiv(kv_len, block_k), key_block,
                              im.flash_init((h,), d))
    o_ref[0] = requantize(im.flash_finalize_q77(state), out_mult, out_shift)


@functools.partial(
    jax.jit,
    static_argnames=("logit_mult", "logit_shift", "out_mult", "out_shift",
                     "block_k", "chunk", "interpret"),
)
def paged_attention_pallas(
    q: jnp.ndarray,  # int8 [B, H, D]: one query row per lane
    k_pool: jnp.ndarray,  # int8 [P+1, Hkv, BS, D]
    v_pool: jnp.ndarray,  # int8 [P+1, Hkv, BS, D]
    table: jnp.ndarray,  # int32 [B, nb]: each lane's blocks, in row order
    kv_len: jnp.ndarray,  # int32 [B]: each lane's key rows, 1..nb*BS
    *,
    logit_mult: int,
    logit_shift: int,
    out_mult: int,
    out_shift: int,
    block_k: int,  # the key split: divides nb*BS, a multiple of BS
    chunk: int,  # rows a pass handles at once: divides block_k, a multiple of BS
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    nb = table.shape[1]
    assert h % hkv == 0 and (nb * bs) % block_k == 0, (h, hkv, nb, bs, block_k)
    assert block_k % chunk == 0 and chunk % bs == 0, (block_k, chunk, bs)
    kernel = functools.partial(
        _paged_kernel, logit_mult=logit_mult, logit_shift=logit_shift,
        out_mult=out_mult, out_shift=out_shift, block_k=block_k, chunk=chunk,
    )
    lane = pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf_bytes = 2 * hkv * block_k * d + 4 * h * block_k
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[lane, pool, pool],
            out_specs=lane,
            scratch_shapes=[
                pltpu.VMEM((hkv, block_k, d), jnp.int8),
                pltpu.VMEM((hkv, block_k, d), jnp.int8),
                pltpu.VMEM((h, block_k), jnp.int32),
                pltpu.SemaphoreType.DMA((2, block_k // chunk)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * buf_bytes),
        ),
        name="paged_attention_pallas",
        interpret=interpret,
    )(kv_len.astype(jnp.int32), table.astype(jnp.int32).reshape(-1), q, k_pool, v_pool)
