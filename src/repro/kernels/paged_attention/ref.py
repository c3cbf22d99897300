"""Pure-jnp oracle for the paged-decode kernel: the XLA runner of the
plan's ``attn_paged`` op, which gathers every lane's whole block table."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.attention import MhaQParams, attention_decode_i8


def norm_table(table_q, b: int) -> jnp.ndarray:
    """A block table as int32 ``[B, nb]`` (one shared row broadcasts)."""
    t = jnp.asarray(table_q, jnp.int32)
    if t.ndim == 1:
        t = t[None]
    return jnp.broadcast_to(t, (b, t.shape[-1]))


def flash_block(block_k: int, rows: int) -> int:
    """The key split of a gathered width of ``rows``: flash blocks of
    ``block_k`` rows, or one block where ``block_k`` does not divide it."""
    bk = min(block_k, rows)
    return rows if rows % bk else bk


def paged_attention_ref(q_m, k_pool, v_pool, pos, table_q, *, heads, kv_heads,
                        head_dim, s_act, s_out, block_k):
    """``q_m`` int8 ``[B, S, H*D]``, pools int8 ``[P+1, Hkv, BS, D]``;
    returns int8 ``[B, S, H*D]``.  Query ``i`` of lane ``b`` sits at row
    ``pos[b] + i`` and attends rows ``[0, pos[b] + i]``."""
    p = MhaQParams.make_flash(s_act, s_act, s_act, s_out, max(head_dim, 1))
    b, s, _ = q_m.shape
    qh = q_m.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)  # [B, H, S, D]
    table_q = norm_table(table_q, b)
    # block-table gather: the slot's logical cache is its blocks
    # concatenated in table order [B, nb*block_size, ...]; rows past
    # the valid prefix (scratch or stale blocks) are masked below, and
    # fully-masked flash updates are bit-neutral, so the gathered
    # width does not change the ints.
    kg = k_pool[table_q].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, -1, head_dim)
    vg = v_pool[table_q].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, -1, head_dim)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    # query i sits at global row pos + i and attends rows [0, pos + i]
    # — causality at a chunk offset expressed as a per-query kv_len
    # bound, so decode (S = 1) and chunked prefill share one runner
    kv_len = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None] + 1
    out = attention_decode_i8(qh, kg, vg, kv_len[:, None, :, None], p,
                              block_k=flash_block(block_k, kg.shape[2]))
    return out.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim)
