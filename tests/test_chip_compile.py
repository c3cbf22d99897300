"""The Pallas kernels compile for a TPU v5e at the widths the main path uses.

Interpret mode accepts programs the TPU kernel compiler refuses (gathers,
int32 matmul operands, unaligned tiles, too much fast memory).  These tests
compile each kernel for a described ``v5e:2x2`` chip, with nothing attached,
and check that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, so the workers that
collect this file but are not given it must not touch it.  Keep every such
compile in this one file.  The tests skip only where no TPU compiler is
installed; any other failure to describe the chip, such as another process
holding the library, fails them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.attention import MhaQParams
from repro.core.igelu import make_igelu_params
from repro.core.quant_linear import ACT_GELU, ACT_IDENTITY
from repro.kernels.expert_gemm.kernel import expert_gemm_pallas
from repro.kernels.expert_gemm.ops import block_n_for
from repro.kernels.igelu.kernel import igelu_pallas
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.ita_attention.kernel import ita_attention_pallas
from repro.kernels.itamax.kernel import itamax_pallas
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ops import chunk_for
from repro.kernels.silumul.kernel import silumul_pallas
from repro.kernels.silumul.ops import ROW_TILE
from repro.models import layers as L
from repro.models.moe import ROW_TILE as MOE_ROW_TILE
from repro.models.moe import grouped_rows
from repro.quant.qparams import make_qparams


@pytest.fixture(scope="module")
def one_chip():
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except RuntimeError as e:
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no TPU compiler is installed: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("act", [ACT_IDENTITY, ACT_GELU], ids=["identity", "gelu"])
def test_int8_gemm_olmo_1b_mlp(one_chip, act):
    """olmo-1b MLP up-projection at a 512-token prefill (M x K x N =
    512 x 2048 x 8192), tiled as the ``ita`` GEMM runner tiles it."""
    gelu = make_igelu_params(0.04)
    qp = make_qparams(gelu.out_scale, 1.0, 0.05)

    def fn(x, w, bias, mult, shift):
        return int8_gemm_pallas(
            x, w, bias, mult, shift, block_m=256, block_n=512, block_k=512,
            act=act, gelu=gelu if act == ACT_GELU else None,
            gelu_mult=qp.mult, gelu_shift=qp.shift, interpret=False,
        )

    text = _compiled_text(
        fn, one_chip,
        ((512, 2048), jnp.int8), ((2048, 8192), jnp.int8),
        ((8192,), jnp.int32), ((8192,), jnp.int32), ((8192,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_igelu(one_chip):
    gelu = make_igelu_params(0.04)
    qp = make_qparams(gelu.out_scale, 1.0, 0.05)

    def fn(x):
        return igelu_pallas(x, gelu=gelu, mult=qp.mult, shift=qp.shift,
                            block_m=256, block_n=512, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, ((512, 8192), jnp.int8))


@pytest.mark.parametrize(
    "heads,seq,head_dim,block_q,block_k,causal",
    [
        (4, 128, 64, 128, 128, False),  # mobilebert, as the ita MHA runner tiles it
        (16, 512, 128, 256, 512, False),  # olmo-1b head shape, the kernel's default tiles
        (16, 512, 128, 128, 128, True),
    ],
    ids=["mobilebert", "16x512x128", "16x512x128-causal"],
)
def test_ita_attention(one_chip, heads, seq, head_dim, block_q, block_k, causal):
    p = MhaQParams.make_flash(0.05, 0.05, 0.05, 0.05, head_dim)

    def fn(q, k, v):
        return ita_attention_pallas(
            q, k, v, group=1, logit_mult=int(p.logit_mult),
            logit_shift=int(p.logit_shift), out_mult=int(p.out_mult),
            out_shift=int(p.out_shift), causal=causal, block_q=block_q,
            block_k=block_k, kv_valid=seq - 3, interpret=False,
        )

    shape = ((8 * heads, seq, head_dim), jnp.int8)  # batch 8
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, shape, shape, shape)


def test_itamax(one_chip):
    """Rowwise ITAMax over mobilebert's attention rows (batch 8, 4 heads,
    128 x 128 logits)."""

    def fn(x):
        return itamax_pallas(x, block_rows=256, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, ((8 * 4 * 128, 128), jnp.int8))


@pytest.mark.parametrize("m", [4096, 32], ids=["chunk", "decode"])
def test_silumul(one_chip, m):
    """SwiGLU's gated product at olmo-1b's MLP width, as the ``ita``
    runner tiles it: the chunk step (32 lanes x 128 tokens) and the decode
    step (32 lanes)."""
    params = L.silumul_params(0.05, 0.05, 0.05)

    def fn(g, u):
        return silumul_pallas(g, u, params=params, block_m=ROW_TILE, block_n=512,
                              interpret=False)

    shape = ((m, 8192), jnp.int8)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, shape, shape)


@pytest.mark.parametrize("pairs", [4096 * 4, 32 * 4], ids=["chunk", "decode"])
def test_silumul_expert_rows(one_chip, pairs):
    """The gated product between Qwen1.5-MoE-A2.7B's grouped expert GEMMs
    (F 1408), told the live row blocks as the ``ita`` runner calls it."""
    rows = grouped_rows(pairs, 60, MOE_ROW_TILE)[1]
    params = L.silumul_params(0.05, 0.05, 0.05)

    def fn(g, u, live):
        return silumul_pallas(g, u, params=params, block_m=ROW_TILE, block_n=128,
                              live=live, interpret=False)

    shape = ((rows, 1408), jnp.int8)
    text = _compiled_text(fn, one_chip, shape, shape, ((1,), jnp.int32))
    assert "tpu_custom_call" in text and "silumul_pallas" in text


@pytest.mark.parametrize("pairs,k,n", [(4096 * 4, 2048, 1408), (32 * 4, 1408, 2048)],
                         ids=["chunk-gate", "decode-down"])
def test_expert_gemm(one_chip, pairs, k, n):
    """Qwen1.5-MoE-A2.7B's grouped expert GEMMs (60 experts of 1408, top-4)
    as the ``ita`` runner tiles them: the gate/up map of a chunk step (32
    lanes x 128 tokens) and the down map of a decode step (32 lanes)."""
    tiles, rows = grouped_rows(pairs, 60, MOE_ROW_TILE)
    qp = make_qparams(0.05, 0.01, 0.05)

    def fn(x, tile_e, live, w):
        vec = jnp.full((n,), qp.mult, jnp.int32)
        return expert_gemm_pallas(x, tile_e, live, w, vec, jnp.full((n,), qp.shift, jnp.int32),
                                  block_m=MOE_ROW_TILE, block_n=block_n_for(k, n),
                                  interpret=False)

    text = _compiled_text(fn, one_chip, ((rows, k), jnp.int8), ((tiles,), jnp.int32),
                          ((1,), jnp.int32), ((60, k, n), jnp.int8))
    assert "tpu_custom_call" in text and "expert_gemm_pallas" in text


def test_paged_decode_attention(one_chip):
    """The decode step's paged attention at olmo-1b's and Qwen1.5-MoE-A2.7B's
    widths (the same for both): 32 lanes, 16 heads of 128, a pool of 4096
    blocks of 16 and the scratch block, tables of 128 blocks, generated rows
    in one key block of 2048."""
    p = MhaQParams.make_flash(0.05, 0.05, 0.05, 0.05, 128)

    def fn(q, k_pool, v_pool, table, kv_len):
        return paged_attention_pallas(
            q, k_pool, v_pool, table, kv_len, logit_mult=int(p.logit_mult),
            logit_shift=int(p.logit_shift), out_mult=int(p.out_mult),
            out_shift=int(p.out_shift), block_k=2048, chunk=chunk_for(2048, 16),
            interpret=False)

    pool = ((4097, 16, 16, 128), jnp.int8)
    text = _compiled_text(fn, one_chip, ((32, 16, 128), jnp.int8), pool, pool,
                          ((32, 128), jnp.int32), ((32,), jnp.int32))
    assert "tpu_custom_call" in text and "paged_attention_pallas" in text
