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
from repro.kernels.igelu.kernel import igelu_pallas
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.ita_attention.kernel import ita_attention_pallas
from repro.kernels.itamax.kernel import itamax_pallas
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
