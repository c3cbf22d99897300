"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Integer kernels admit *bit-exact* checks (no tolerance): any mismatch is a
real bug, not numerics.  Accuracy vs float references is covered by the
core tests; here we sweep shapes/blocks and assert exact equality.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant_linear import ACT_GELU, ACT_IDENTITY, ACT_RELU
from repro.kernels import (
    igelu,
    igelu_ref,
    int8_gemm,
    int8_gemm_ref,
    ita_attention,
    ita_attention_ref,
    itamax,
    itamax_ref,
    silumul,
    silumul_ref,
)


def _ri8(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)


class TestInt8GemmKernel:
    @pytest.mark.parametrize(
        "m,k,n,bm,bn,bk",
        [
            (128, 128, 128, 128, 128, 128),
            (256, 512, 128, 128, 128, 256),
            (128, 1024, 256, 64, 128, 512),
        ],
    )
    @pytest.mark.parametrize("act", [ACT_IDENTITY, ACT_RELU, ACT_GELU])
    def test_bit_exact_vs_oracle(self, m, k, n, bm, bn, bk, act):
        rng = np.random.default_rng(m + n + act)
        x, w = _ri8(rng, (m, k)), _ri8(rng, (k, n))
        bias = jnp.asarray(rng.integers(-1000, 1000, size=(n,)), jnp.int32)
        kw = dict(s_in=0.02, s_w=0.005, s_out=0.05, act=act, s_preact=0.04)
        got = int8_gemm(x, w, bias, block_m=bm, block_n=bn, block_k=bk, **kw)
        want = int8_gemm_ref(x, w, bias, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_per_channel_scales(self):
        rng = np.random.default_rng(7)
        x, w = _ri8(rng, (128, 256)), _ri8(rng, (256, 128))
        s_w = rng.uniform(0.001, 0.01, size=(128,))
        kw = dict(s_in=0.02, s_w=s_w, s_out=0.05, act=ACT_IDENTITY)
        got = int8_gemm(x, w, None, block_m=128, block_n=128, block_k=128, **kw)
        want = int8_gemm_ref(x, w, None, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_batched_leading_dims(self):
        rng = np.random.default_rng(8)
        x = _ri8(rng, (2, 4, 64, 128))
        w = _ri8(rng, (128, 128))
        kw = dict(s_in=0.02, s_w=0.004, s_out=0.03)
        got = int8_gemm(x, w, None, block_m=128, block_n=128, block_k=128, **kw)
        want = int8_gemm_ref(x, w, None, **kw)
        assert got.shape == (2, 4, 64, 128)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestItaAttentionKernel:
    @pytest.mark.parametrize(
        "b,h,hkv,sq,sk,d,bq,bk",
        [
            (1, 2, 2, 128, 128, 64, 128, 128),
            (2, 4, 2, 128, 256, 64, 64, 128),
            (1, 8, 1, 64, 512, 128, 64, 256),  # MQA
        ],
    )
    @pytest.mark.parametrize("causal", [False, True])
    def test_bit_exact_vs_oracle(self, b, h, hkv, sq, sk, d, bq, bk, causal):
        rng = np.random.default_rng(b * h + sk)
        q = _ri8(rng, (b, h, sq, d))
        k = _ri8(rng, (b, hkv, sk, d))
        v = _ri8(rng, (b, hkv, sk, d))
        kw = dict(s_q=0.02, s_k=0.02, s_v=0.02, s_out=0.02, causal=causal)
        got = ita_attention(q, k, v, block_q=bq, block_k=bk, **kw)
        want = ita_attention_ref(q, k, v, block_k=bk, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_accuracy_vs_float(self):
        from repro.core import attention as attn
        from repro.core import itamax as im

        rng = np.random.default_rng(11)
        b, h, s, d = 1, 4, 256, 64
        qf = rng.normal(size=(b, h, s, d)).astype(np.float32)
        kf = rng.normal(size=(b, h, s, d)).astype(np.float32)
        vf = rng.normal(size=(b, h, s, d)).astype(np.float32)
        s_q, s_k, s_v = (float(np.abs(t).max() / 127) for t in (qf, kf, vf))
        ref = np.asarray(
            attn.attention_f32(
                jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf), causal=True,
                logit_clip=127 * im.ITAMAX_LOGIT_SCALE,
            )
        )
        s_out = float(np.abs(ref).max() / 127) + 1e-9
        from repro.quant.qparams import quantize_array

        got = np.asarray(
            ita_attention(
                quantize_array(jnp.asarray(qf), s_q),
                quantize_array(jnp.asarray(kf), s_k),
                quantize_array(jnp.asarray(vf), s_v),
                s_q=s_q, s_k=s_k, s_v=s_v, s_out=s_out,
                causal=True, block_q=128, block_k=128,
            ),
            np.float32,
        ) * s_out
        assert np.max(np.abs(got - ref)) < 0.08 * np.abs(ref).max() + 6 * s_out


class TestItamaxKernel:
    @pytest.mark.parametrize("r,n,br", [(256, 128, 128), (512, 512, 256), (128, 64, 64)])
    def test_bit_exact_vs_oracle(self, r, n, br):
        rng = np.random.default_rng(r + n)
        x = _ri8(rng, (r, n))
        got = itamax(x, block_rows=br)
        want = itamax_ref(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_leading_dims(self):
        rng = np.random.default_rng(3)
        x = _ri8(rng, (2, 8, 16, 128))
        got = itamax(x, block_rows=128)
        want = itamax_ref(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestIGeluKernel:
    @pytest.mark.parametrize("m,n,bm,bn", [(128, 512, 128, 256), (256, 1024, 128, 512)])
    @pytest.mark.parametrize("scale", [0.02, 0.08])
    def test_bit_exact_vs_oracle(self, m, n, bm, bn, scale):
        rng = np.random.default_rng(int(scale * 1000))
        x = _ri8(rng, (m, n))
        got = igelu(x, in_scale=scale, out_scale=scale, block_m=bm, block_n=bn)
        want = igelu_ref(x, in_scale=scale, out_scale=scale)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _code_pairs(*shape):
    """Gate and up codes that run through the 256 x 256 (gate, up) pairs in
    order, again from the start after the last; every pair once in 65,536
    elements."""
    idx = np.arange(int(np.prod(shape))) % (256 * 256)
    g = (idx // 256 - 128).astype(np.int8).reshape(shape)
    u = (idx % 256 - 128).astype(np.int8).reshape(shape)
    return jnp.asarray(g), jnp.asarray(u)


OLMO_SCALES = (0.05, 0.05, 0.05)


class TestSilumulKernel:
    """The ita runner of SwiGLU's gated product against the XLA runner."""

    @pytest.mark.parametrize("scales", [OLMO_SCALES, (0.02, 0.08, 0.05), (0.1, 0.03, 0.07)],
                             ids=["olmo-1b", "narrow-gate", "wide-gate"])
    def test_every_code_pair(self, scales):
        g, u = _code_pairs(256, 256)
        got = silumul(g, u, scales=scales)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(silumul_ref(g, u, scales=scales)))

    # M = 1 and 96 pad rows up to the int8 row tile; 4096 x 8192 is
    # olmo-1b's chunk step (32 lanes x 128 tokens), 32 x 8192 its decode step
    @pytest.mark.parametrize("f", [128, 8192])
    @pytest.mark.parametrize("m", [1, 32, 96, 4096])
    def test_shapes(self, m, f):
        g, u = _code_pairs(m, f)
        got = silumul(g, u, scales=OLMO_SCALES)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(silumul_ref(g, u, scales=OLMO_SCALES)))

    @pytest.mark.parametrize("shape", [(2, 3, 8192), (32, 1, 8192), (4, 16, 256)])
    def test_leading_dims(self, shape):
        g, u = _code_pairs(*shape)
        got = silumul(g, u, scales=OLMO_SCALES)
        assert got.shape == shape and got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(silumul_ref(g, u, scales=OLMO_SCALES)))


class TestItaDecodeKernel:
    """Fused decode step: GQA head-grouping as query rows, kv_valid mask."""

    @pytest.mark.parametrize("h,hkv,smax,fill,bk", [(8, 2, 256, 200, 64), (4, 1, 512, 512, 128)])
    def test_bit_exact_vs_serving_path(self, h, hkv, smax, fill, bk):
        from repro.core.attention import MhaQParams, attention_decode_i8
        from repro.kernels.ita_attention.ops import ita_decode

        rng = np.random.default_rng(h + smax)
        b, d = 2, 64
        q = _ri8(rng, (b, h, 1, d))
        kc = _ri8(rng, (b, hkv, smax, d))
        vc = _ri8(rng, (b, hkv, smax, d))
        # zero the unfilled tail like a real cache
        import jax.numpy as jnp

        mask = (np.arange(smax) < fill)[None, None, :, None]
        kc = jnp.asarray(np.asarray(kc) * mask, jnp.int8)
        vc = jnp.asarray(np.asarray(vc) * mask, jnp.int8)
        scales = dict(s_q=0.02, s_k=0.02, s_v=0.02, s_out=0.02)
        got = ita_decode(q, kc, vc, fill, block_k=bk, **scales)
        p = MhaQParams.make_flash(0.02, 0.02, 0.02, 0.02, d)
        want = attention_decode_i8(
            q, kc, vc, jnp.full((b,), fill, jnp.int32), p, block_k=bk
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
