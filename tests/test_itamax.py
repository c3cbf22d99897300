"""Tests for ITAMax: paper-faithful rowwise + flash-blocked forms."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import itamax as im


def _rand_logits(rng, shape, lo=-128, hi=127):
    return jnp.asarray(rng.integers(lo, hi + 1, size=shape), jnp.int8)


class TestRowwise:
    @pytest.mark.parametrize("n", [16, 64, 128, 512])
    def test_close_to_float_softmax(self, n):
        rng = np.random.default_rng(0)
        x = _rand_logits(rng, (8, n))
        a = np.asarray(im.itamax_rowwise(x), np.float32) * im.A_SCALE
        ref = np.asarray(
            im.itamax_rowwise_f32(jnp.asarray(x, jnp.float32) * im.ITAMAX_LOGIT_SCALE)
        )
        # 8-bit A: absolute error bounded by ~1.5 LSB + LUT error
        assert np.max(np.abs(a - ref)) < 2.5 * im.A_SCALE, np.max(np.abs(a - ref))

    def test_rows_track_float_softmax_elementwise(self):
        """A == round(128 * softmax(dequantized logits)) within ~2 LSB."""
        rng = np.random.default_rng(1)
        for n in (64, 256, 512):
            x = _rand_logits(rng, (16, n))
            a = np.asarray(im.itamax_rowwise(x), np.int32)
            p = np.asarray(
                im.itamax_rowwise_f32(
                    jnp.asarray(x, jnp.float32) * im.ITAMAX_LOGIT_SCALE
                )
            )
            want = np.round(128 * p)
            assert np.max(np.abs(a - want)) <= 2

    def test_diffuse_rows_bounded_mass_loss(self):
        """8-bit A truncates sub-LSB probabilities: diffuse rows lose mass.

        This is inherent to ITA's 8-bit EN stage (documented in DESIGN.md);
        we pin the behaviour so regressions are visible.
        """
        rng = np.random.default_rng(1)
        x = _rand_logits(rng, (32, 256))
        a = np.asarray(im.itamax_rowwise(x), np.float32) * im.A_SCALE
        s = a.sum(-1)
        assert (s <= 1.02).all()
        assert (s >= 0.75).all()  # measured ~0.83-0.95 for uniform logits

    def test_one_hot_row(self):
        """int8 logits span +-2.77 real units (S=ln2/32): a '+127 one-hot'
        row keeps ~20% tail mass in float softmax too — check against it."""
        x = jnp.full((1, 64), -128, jnp.int8).at[0, 7].set(127)
        a = np.asarray(im.itamax_rowwise(x), np.int32)
        p = np.asarray(
            im.itamax_rowwise_f32(
                jnp.asarray(x, jnp.float32) * im.ITAMAX_LOGIT_SCALE
            )
        )
        want = np.round(128 * p)
        assert np.argmax(a[0]) == 7
        assert np.max(np.abs(a - want)) <= 2

    def test_uniform_row(self):
        x = jnp.zeros((1, 128), jnp.int8)
        a = np.asarray(im.itamax_rowwise(x), np.float32) * im.A_SCALE
        np.testing.assert_allclose(a, 1.0 / 128, atol=im.A_SCALE)

    def test_mask(self):
        rng = np.random.default_rng(2)
        x = _rand_logits(rng, (4, 64))
        mask = jnp.arange(64) < 40
        a = np.asarray(im.itamax_rowwise(x, mask=mask[None, :]), np.float32)
        assert (a[:, 40:] == 0).all()
        np.testing.assert_allclose(a[:, :40].sum(-1) * im.A_SCALE, 1.0, atol=0.05)

class TestFlash:
    @pytest.mark.parametrize("n,block", [(64, 16), (256, 64), (512, 128), (1024, 128)])
    def test_matches_float_attention(self, n, block):
        rng = np.random.default_rng(3)
        logits = _rand_logits(rng, (4, n))
        v = _rand_logits(rng, (n, 32))
        q77 = np.asarray(im.flash_itamax_reference(logits, jnp.asarray(v), block))
        got = q77.astype(np.float32) * 2.0**-7  # in units of V's int grid
        p = np.asarray(
            im.itamax_rowwise_f32(
                jnp.asarray(logits, jnp.float32) * im.ITAMAX_LOGIT_SCALE
            )
        )
        want = p @ np.asarray(v, np.float32)
        # |V| <= 127 -> absolute tolerance in V units
        assert np.max(np.abs(got - want)) < 1.5, np.max(np.abs(got - want))

    def test_block_invariance_is_bounded(self):
        """Different block sizes must agree closely (not bit-exact: the
        renormalization schedule differs)."""
        rng = np.random.default_rng(4)
        logits = _rand_logits(rng, (4, 512))
        v = _rand_logits(rng, (512, 16))
        a = np.asarray(im.flash_itamax_reference(logits, jnp.asarray(v), 64))
        b = np.asarray(im.flash_itamax_reference(logits, jnp.asarray(v), 128))
        assert np.max(np.abs(a - b)) <= 64  # < 0.5 in V units at Q7.7

    def test_long_row_no_overflow(self):
        """500k-element rows stay inside int32 (magnitude guard)."""
        rng = np.random.default_rng(5)
        n = 8192  # long enough to trip the rescale guard many times
        logits = jnp.zeros((2, n), jnp.int8)  # worst case: all equal max
        v = _rand_logits(rng, (n, 8))
        q77 = np.asarray(im.flash_itamax_reference(logits, jnp.asarray(v), 512))
        got = q77.astype(np.float32) * 2.0**-7
        want = np.asarray(v, np.float32).mean(0)
        assert np.max(np.abs(got - want)) < 1.5

    def test_causal_mask(self):
        rng = np.random.default_rng(6)
        n = 128
        logits = _rand_logits(rng, (n, n))
        v = _rand_logits(rng, (n, 16))
        mask = np.tril(np.ones((n, n), bool))
        q77 = np.asarray(
            im.flash_itamax_reference(
                logits, jnp.asarray(v), 32, mask=jnp.asarray(mask)
            )
        )
        got = q77.astype(np.float32) * 2.0**-7
        lf = np.asarray(logits, np.float32) * im.ITAMAX_LOGIT_SCALE
        lf = np.where(mask, lf, -1e9)
        p = np.exp(lf - lf.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = p @ np.asarray(v, np.float32)
        assert np.max(np.abs(got - want)) < 1.5


class TestExpLut:
    def test_lut_values(self):
        lut = im.EXP_LUT
        want = np.round(256 * 2.0 ** (-np.arange(32) / 32))
        np.testing.assert_array_equal(lut, want)

    def test_exp2_decomposition(self):
        # exp over the full int8 delta range tracks 2^(-t/32)
        t = jnp.arange(0, 256, dtype=jnp.int32)
        val = np.asarray(im._exp2_int(t, im.EXP_LUT, im.EXP_LUT_BITS), np.float64)
        want = 256 * 2.0 ** (-np.arange(256) / 32.0)
        assert np.max(np.abs(val - want)) <= 1.0

    @pytest.mark.parametrize("lookup", ["lut_lookup", "lut_gather"])
    @pytest.mark.parametrize("table", ["EXP_LUT", "EXP_LUT7", "RENORM_LUT"])
    def test_lookup_equals_indexing(self, table, lookup):
        """The select-tree lookup (which the TPU kernel compiler accepts)
        and the XLA gather return exactly ``lut[r]`` for every index, in
        any array shape."""
        lut, fn = getattr(im, table), getattr(im, lookup)
        r = jnp.arange(32, dtype=jnp.int32)
        want = jnp.asarray(lut)[r]
        np.testing.assert_array_equal(np.asarray(fn(lut, r)), np.asarray(want))
        r2 = jnp.asarray(np.random.default_rng(0).integers(0, 32, (8, 64)), jnp.int32)
        got = fn(lut, r2)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), lut[np.asarray(r2)])
