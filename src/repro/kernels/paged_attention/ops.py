"""The ``ita`` runner of the plan's ``attn_paged`` op at one query row
per lane (the decode step): the plan's merged ``[B, 1, H*D]`` layout onto
the kernel's ``[B, H, D]``, the key split of the XLA runner, and the
chunk of rows a pass handles."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.attention import MhaQParams
from repro.kernels.interpret import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import flash_block, norm_table, paged_attention_ref

#: rows a pass handles at once: one 128-lane tile of logits
CHUNK_ROWS = 128


def chunk_for(block_k: int, block_size: int) -> int:
    """The largest multiple of ``block_size`` up to ``CHUNK_ROWS`` (or one
    block) that divides ``block_k``."""
    top = max(CHUNK_ROWS, block_size)
    return max(c for c in range(block_size, top + 1, block_size) if block_k % c == 0)


def paged_attention(q_m, k_pool, v_pool, pos, table_q, *, heads, kv_heads, head_dim,
                    s_act, s_out, block_k):
    """``paged_attention_ref``'s signature and ints, for ``q_m`` int8
    ``[B, 1, H*D]``.  A key split finer than a pool block (``block_k`` not
    a multiple of it) runs the XLA runner."""
    b, s, _ = q_m.shape
    assert s == 1, "the paged-decode kernel takes one query row per lane"
    block_size = k_pool.shape[2]
    table_q = norm_table(table_q, b)
    bk = flash_block(block_k, table_q.shape[1] * block_size)
    if bk % block_size:
        return paged_attention_ref(q_m, k_pool, v_pool, pos, table_q, heads=heads,
                                   kv_heads=kv_heads, head_dim=head_dim, s_act=s_act,
                                   s_out=s_out, block_k=block_k)
    p = MhaQParams.make_flash(s_act, s_act, s_act, s_out, max(head_dim, 1))
    kv_len = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,)) + 1
    out = paged_attention_pallas(
        q_m.reshape(b, heads, head_dim), k_pool, v_pool, table_q, kv_len,
        logit_mult=int(p.logit_mult), logit_shift=int(p.logit_shift),
        out_mult=int(p.out_mult), out_shift=int(p.out_shift),
        block_k=bk, chunk=chunk_for(bk, block_size), interpret=interpret_mode(),
    )
    return out.reshape(b, 1, heads * head_dim)
