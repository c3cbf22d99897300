"""jit'd public wrapper for the fused ITA attention kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.attention import MhaQParams
from repro.kernels.interpret import interpret_mode
from repro.kernels.ita_attention.kernel import ita_attention_pallas


def ita_attention(
    q_q: jnp.ndarray,  # int8 [B, H, Sq, D]
    k_q: jnp.ndarray,  # int8 [B, Hkv, Sk, D]
    v_q: jnp.ndarray,  # int8 [B, Hkv, Sk, D]
    *,
    s_q: float,
    s_k: float,
    s_v: float,
    s_out: float,
    causal: bool = False,
    block_q: int = 256,
    block_k: int = 512,
    kv_valid: int | None = None,
) -> jnp.ndarray:
    """Fused int8 MHA with streaming ITAMax. Returns int8 [B, H, Sq, D].

    Bit-exact vs ``attention_flash_i8`` with the same ``block_k``.
    ``kv_valid`` masks padded KV rows (callers that pad Sk to a block
    multiple pass the true length).
    """
    b, h, sq, d = q_q.shape
    _, hkv, sk, _ = k_q.shape
    assert h % hkv == 0
    p = MhaQParams.make_flash(s_q, s_k, s_v, s_out, d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    out = ita_attention_pallas(
        q_q.reshape(b * h, sq, d),
        k_q.reshape(b * hkv, sk, d),
        v_q.reshape(b * hkv, sk, d),
        group=h // hkv,
        logit_mult=int(p.logit_mult),
        logit_shift=int(p.logit_shift),
        out_mult=int(p.out_mult),
        out_shift=int(p.out_shift),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_valid=kv_valid,
        interpret=interpret_mode(),
    )
    return out.reshape(b, h, sq, d)


def ita_decode(
    q_q: jnp.ndarray,  # int8 [B, H, 1, D] — one new token per sequence
    k_cache: jnp.ndarray,  # int8 [B, Hkv, Smax, D]
    v_cache: jnp.ndarray,  # int8 [B, Hkv, Smax, D]
    cache_len: int,  # valid prefix of the cache (static per serving bucket)
    *,
    s_q: float,
    s_k: float,
    s_v: float,
    s_out: float,
    block_k: int = 512,
) -> jnp.ndarray:
    """Fused int8 decode step (serve_step hot loop).

    The sq=1 row would waste the MXU, so the GQA *query heads that share a
    KV head* are batched as query rows: q reshapes to [B*Hkv, G, D] and
    attends its group's cache slice — G useful rows per grid step instead
    of 1 (the flash-decoding head-batching trick, int8 flavor).  Masking
    of the unfilled cache tail reuses the kernel's ``kv_valid``; serving
    buckets cache lengths so ``cache_len`` is static per compiled variant
    (dynamic lengths would use scalar prefetch — noted in DESIGN.md).
    """
    b, h, sq, d = q_q.shape
    assert sq == 1, "decode takes exactly one new token"
    _, hkv, smax, _ = k_cache.shape
    g = h // hkv
    p = MhaQParams.make_flash(s_q, s_k, s_v, s_out, d)
    out = ita_attention_pallas(
        # heads of one group become the query rows of one grid step
        q_q.reshape(b, hkv, g, d).reshape(b * hkv, g, d),
        k_cache.reshape(b * hkv, smax, d),
        v_cache.reshape(b * hkv, smax, d),
        group=1,
        logit_mult=int(p.logit_mult),
        logit_shift=int(p.logit_shift),
        out_mult=int(p.out_mult),
        out_shift=int(p.out_shift),
        causal=False,
        block_q=g,
        block_k=min(block_k, smax),
        kv_valid=cache_len,
        interpret=interpret_mode(),
    )
    return out.reshape(b, h, 1, d)
