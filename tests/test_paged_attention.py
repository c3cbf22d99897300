"""The Pallas paged-decode kernel is bit-exact against the XLA runner of
``attn_paged`` (interpret mode on the CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import MhaQParams
from repro.deploy.paging import SCRATCH_BLOCK
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ops import chunk_for

BS, NB, D, HEADS = 16, 4, 8, 4  # max_len 64
MAX_LEN = NB * BS
S_ACT, S_OUT = 0.01, 0.004  # logits spread about 25 steps: few saturate


def _case(seed, kv_heads, lens, inactive=()):
    """Random codes over the whole pool (past every lane's length too),
    shuffled non-contiguous tables with stale blocks past each length,
    lane 1 sharing lane 0's first block, ``inactive`` lanes on the scratch
    block at position 0."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    pool = (len(lens) * NB + 1, kv_heads, BS, D)
    k_pool = jnp.asarray(rng.integers(-128, 128, pool), jnp.int8)
    v_pool = jnp.asarray(rng.integers(-128, 128, pool), jnp.int8)
    blocks = rng.permutation(np.arange(1, pool[0]))
    table = blocks[: b * NB].reshape(b, NB).astype(np.int32)
    table[1, 0] = table[0, 0]
    pos = np.asarray(lens, np.int32) - 1
    for lane in inactive:
        table[lane] = SCRATCH_BLOCK
        pos[lane] = 0
    q = jnp.asarray(rng.integers(-128, 128, (b, 1, HEADS * D)), jnp.int8)
    return q, k_pool, v_pool, jnp.asarray(pos), jnp.asarray(table)


LENS = [1, 15, 16, 17, 32, 33, MAX_LEN, 5]  # 32: the edge of block_k 32


@pytest.mark.parametrize("block_k", [MAX_LEN, 32], ids=["one-key-block", "two-key-blocks"])
@pytest.mark.parametrize("kv_heads", [HEADS, HEADS // 2], ids=["mha", "gqa2"])
def test_kernel_equals_the_xla_runner(kv_heads, block_k):
    args = _case(kv_heads * 100 + block_k, kv_heads, LENS, inactive=(7,))
    kw = dict(heads=HEADS, kv_heads=kv_heads, head_dim=D, s_act=S_ACT, s_out=S_OUT,
              block_k=block_k)
    out = np.asarray(paged_attention(*args, **kw))
    ref = np.asarray(paged_attention_ref(*args, **kw))
    assert out.shape == (len(LENS), 1, HEADS * D)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("chunk", [16, 32], ids=["chunk16", "chunk32"])
def test_several_chunks_a_key_block(chunk):
    """A key block handled in several passes' chunks, past a partly
    fetched chunk: the same ints as one flash update over the block."""
    q, k_pool, v_pool, pos, table = _case(11, HEADS // 2, LENS)
    kw = dict(heads=HEADS, kv_heads=HEADS // 2, head_dim=D, s_act=S_ACT, s_out=S_OUT,
              block_k=MAX_LEN)
    p = MhaQParams.make_flash(S_ACT, S_ACT, S_ACT, S_OUT, D)
    out = paged_attention_pallas(
        q.reshape(len(LENS), HEADS, D), k_pool, v_pool, table, pos + 1,
        logit_mult=int(p.logit_mult), logit_shift=int(p.logit_shift),
        out_mult=int(p.out_mult), out_shift=int(p.out_shift),
        block_k=MAX_LEN, chunk=chunk, interpret=True)
    ref = paged_attention_ref(q, k_pool, v_pool, pos, table, **kw)
    np.testing.assert_array_equal(np.asarray(out).reshape(ref.shape), np.asarray(ref))


def test_rows_past_the_length_do_not_count():
    """Rewriting every pool row past each lane's length (in its own blocks
    and in the stale blocks its table names) changes nothing."""
    lens = [3, 16, 17, 40]
    q, k_pool, v_pool, pos, table = _case(5, HEADS, lens)
    kw = dict(heads=HEADS, kv_heads=HEADS, head_dim=D, s_act=S_ACT, s_out=S_OUT,
              block_k=MAX_LEN)
    out = np.asarray(paged_attention(q, k_pool, v_pool, pos, table, **kw))
    live = np.zeros(k_pool.shape[0:1] + (BS,), bool)
    t = np.asarray(table)
    for lane, n in enumerate(lens):
        for r in range(n):
            live[t[lane, r // BS], r % BS] = True
    mask = jnp.asarray(live[:, None, :, None])
    k2 = jnp.where(mask, k_pool, jnp.int8(127))
    v2 = jnp.where(mask, v_pool, jnp.int8(-128))
    np.testing.assert_array_equal(
        np.asarray(paged_attention(q, k2, v2, pos, table, **kw)), out)


@pytest.mark.parametrize("block_k,block_size,chunk", [
    (2048, 16, 128), (512, 16, 128), (64, 16, 64), (48, 16, 48), (40, 8, 40), (32, 32, 32),
])
def test_chunk_divides_the_key_block(block_k, block_size, chunk):
    assert chunk_for(block_k, block_size) == chunk
