"""Integer arithmetic of the w8a8 model, written out plainly for the reference.

A frozen copy of the program's integer primitives (fixed-point requantization,
integer LayerNorm, i-GeLU, integer SiLU, Q0.7 RoPE, flash ITAMax), kept with
the benchmark so that the reference imports nothing of the program: a later
change to the program's arithmetic is then measured against what the
configuration states, not against itself.  Every function here is pure
``jax.numpy`` on int32 values and computes the same integers as the program
is specified to; nothing in it runs a kernel, a plan, a cache or a batch.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INT8_MIN, INT8_MAX = -128, 127
MULT_MAX = (1 << 15) - 1
SHIFT_MIN, SHIFT_MAX = 10, 31
_DECOMP_BITS = 10
_DECOMP_MASK = (1 << _DECOMP_BITS) - 1


# -- fixed-point requantization ------------------------------------------------

def quantize_multiplier(real: float) -> tuple[int, int]:
    """``real`` as ``mult * 2^-shift`` with a 15-bit ``mult``."""
    if real <= 0:
        return 0, SHIFT_MIN
    shift = max(SHIFT_MIN, min(SHIFT_MAX, int(math.floor(math.log2(MULT_MAX / real)))))
    mult = min(int(round(real * (1 << shift))), MULT_MAX)
    if mult == 0:
        shift = SHIFT_MAX
        mult = max(0, int(round(real * (1 << shift))))
    return mult, shift


def np_quantize_multiplier(real: float) -> tuple[int, int]:
    """The vectorised (numpy) rule the linear sites use; scalar here."""
    r = max(float(real), 1e-30)
    shift = int(np.clip(np.floor(np.log2(MULT_MAX / r)), SHIFT_MIN, SHIFT_MAX))
    mult = int(np.clip(np.rint(r * (2.0 ** shift)), 0, MULT_MAX))
    return mult, shift


def qparams(s_in: float, s_w: float, s_out: float) -> tuple[int, int]:
    return quantize_multiplier((s_in * s_w) / s_out)


def _round_mul_shift(acc, mult, shift):
    acc = jnp.asarray(acc, jnp.int32)
    hi = acc >> _DECOMP_BITS
    lo = acc & _DECOMP_MASK
    c = lo * jnp.int32(mult) + (jnp.int32(1) << (shift - 1))
    return (hi * jnp.int32(mult) + (c >> _DECOMP_BITS)) >> (shift - _DECOMP_BITS)


def requantize(acc, mult: int, shift: int):
    """int32 -> int8: ``clip(round_half_up(acc * mult / 2^shift))``."""
    return jnp.clip(_round_mul_shift(acc, mult, shift), INT8_MIN, INT8_MAX).astype(jnp.int8)


def requantize_wide(acc, mult: int, shift: int, out_bits: int = 16):
    lim = 1 << (out_bits - 1)
    return jnp.clip(_round_mul_shift(acc, mult, shift), -lim, lim - 1).astype(jnp.int32)


def rshift_round(x, shift):
    """Round-half-up arithmetic right shift for shift in 0..31."""
    x = jnp.asarray(x, jnp.int32)
    shift = jnp.asarray(shift, jnp.int32)
    bias = jnp.where(shift > 0, jnp.int32(1) << jnp.maximum(shift - 1, 0), 0)
    return (x + bias) >> shift


# -- ITAMax exponentials -------------------------------------------------------

ITAMAX_B = 5
_FRAC = (1 << ITAMAX_B) - 1
ITAMAX_LOGIT_SCALE = math.log(2.0) / (1 << ITAMAX_B)
EXP_LUT8 = np.round(256 * 2.0 ** (-np.arange(32) / 32.0)).astype(np.int32)
EXP_LUT7 = np.minimum(np.round(128 * 2.0 ** (-np.arange(32) / 32.0)), 127).astype(np.int32)
RENORM_LUT = np.round(1024 * 2.0 ** (-np.arange(32) / 32.0)).astype(np.int32)
RESCALE_THRESH, RESCALE_BITS = 1 << 21, 8
M_SENTINEL = -(1 << 15)


def lut(table: np.ndarray, r):
    """``table[r]`` for r in [0, 32)."""
    return jnp.asarray(table, jnp.int32)[r]


def exp2_int(t, table: np.ndarray):
    """``round(2^bits * 2^(-t / 32))`` for t >= 0, half-up on the shift."""
    t = jnp.asarray(t, jnp.int32)
    q = jnp.minimum(t >> ITAMAX_B, 31)
    bias = jnp.where(q > 0, jnp.int32(1) << jnp.maximum(q - 1, 0), 0)
    return (lut(table, t & _FRAC) + bias) >> q


def _mul_q10(x, mult):
    hi = x >> 10
    lo = x & 1023
    return hi * mult + ((lo * mult + 512) >> 10)


def _renorm(x, delta):
    """``x * 2^(-delta / 32)`` for delta >= 0."""
    return _mul_q10(rshift_round(x, jnp.minimum(delta >> ITAMAX_B, 31)),
                    lut(RENORM_LUT, delta & _FRAC))


class FlashState(NamedTuple):
    m: jnp.ndarray
    d: jnp.ndarray
    acc: jnp.ndarray


def flash_update(st: FlashState, logits_i8, v_i8, mask) -> FlashState:
    """One key block of flash ITAMax: running max, renormalised sums, A.V."""
    x = jnp.asarray(jnp.where(mask, logits_i8, jnp.int8(-128)), jnp.int32)
    new_m = jnp.maximum(st.m, jnp.max(x, axis=-1, keepdims=True))
    delta = jnp.clip(new_m - st.m, 0, 1 << 12)
    d = _renorm(st.d, delta)
    acc = _renorm(st.acc, delta[..., 0:1])
    val = jnp.where(mask, exp2_int(jnp.clip(new_m - x, 0, 1 << 20), EXP_LUT7), 0)
    d = d + jnp.sum(val, axis=-1, keepdims=True)
    acc = acc + jnp.einsum("...qk,...kd->...qd", val.astype(jnp.int8), v_i8,
                           preferred_element_type=jnp.int32)
    over = d > RESCALE_THRESH
    return FlashState(new_m, jnp.where(over, rshift_round(d, RESCALE_BITS), d),
                      jnp.where(over, rshift_round(acc, RESCALE_BITS), acc))


def flash_finalize(st: FlashState):
    """Q7.7 ``acc / d`` with exact integer division."""
    d = jnp.maximum(st.d, 1)
    r = jnp.floor_divide(st.acc, d)
    frac = jnp.floor_divide(((st.acc - r * d) << 7) + (d >> 1), d)
    return r * 128 + frac


def attention(q, k, v, q_rows, block: int, s_act: float, head_dim: int):
    """Flash-ITAMax attention of int8 ``q`` [B,H,Sq,D] over ``k``/``v``
    [B,H,Sk,D] in key blocks of ``block`` rows starting at row 0.  Query i
    sits at key row ``q_rows[i]`` and attends rows ``[0, q_rows[i]]``."""
    lm, ls = qparams(s_act, s_act / math.sqrt(head_dim), ITAMAX_LOGIT_SCALE)
    om, osh = qparams(2.0 ** -7, s_act, s_act)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    st = FlashState(jnp.full((b, h, sq, 1), M_SENTINEL, jnp.int32),
                    jnp.zeros((b, h, sq, 1), jnp.int32),
                    jnp.zeros((b, h, sq, d), jnp.int32))
    for lo in range(0, sk, block):
        kb, vb = k[:, :, lo:lo + block], v[:, :, lo:lo + block]
        logits = requantize(jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                                       preferred_element_type=jnp.int32), lm, ls)
        rows = lo + jnp.arange(kb.shape[2], dtype=jnp.int32)
        mask = rows[None, :] <= jnp.asarray(q_rows, jnp.int32)[:, None]
        st = flash_update(st, logits, vb, jnp.broadcast_to(mask, logits.shape))
    return requantize(flash_finalize(st), om, osh)


# -- norms, activations, linear sites -----------------------------------------

NORM_BITS = 10
NORM_SCALE = 2.0 ** -NORM_BITS


def isqrt(v):
    v = jnp.asarray(v, jnp.int32)

    def body(_, x):
        xs = jnp.maximum(x, 1)
        return jnp.minimum(x, (xs + v // xs) >> 1)

    x = jax.lax.fori_loop(0, 20, body, jnp.full(v.shape, 1 << 16, jnp.int32))
    x = jnp.clip(x, 1, 46340)
    x = jnp.where(x * x > v, x - 1, x)
    x = jnp.where(x * x > v, x - 1, x)
    return jnp.maximum(x, 1)


def _normalize(x_i8):
    x = jnp.asarray(x_i8, jnp.int32)
    n = x.shape[-1]
    mu = jnp.sum(x, axis=-1, keepdims=True)
    mu = jnp.where(mu >= 0, (mu + n // 2) // n, -((-mu + n // 2) // n))
    xc = x - mu
    sigma = isqrt(jnp.sum(xc * xc, axis=-1, keepdims=True) // n)
    return (xc << NORM_BITS) // sigma


def layernorm(x_i8, s_out: float, gamma=None, beta=None, s_gamma: float = 1.0 / 64):
    """Integer LayerNorm; no ``gamma`` is the non-parametric form."""
    n = _normalize(x_i8)
    if gamma is None:
        return requantize(n, *qparams(NORM_SCALE, 1.0, s_out))
    acc = n * jnp.asarray(gamma, jnp.int32) + jnp.asarray(beta, jnp.int32)
    return requantize(acc, *qparams(NORM_SCALE, s_gamma, s_out))


def iadd(a_i8, b_i8, s_a: float, s_b: float, s_out: float):
    """Residual add: both operands onto ``s_out``, saturating."""
    a = requantize_wide(a_i8, *qparams(s_a, 1.0, s_out))
    b = requantize_wide(b_i8, *qparams(s_b, 1.0, s_out))
    return jnp.clip(a + b, -128, 127).astype(jnp.int8)


def rope(x_i8, positions, theta: float):
    """Q0.7 rotary embedding of int8 ``x`` [B,H,S,D] at ``positions`` [S]."""
    half = x_i8.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.asarray(positions)[..., None].astype(jnp.float32) * freqs
    c = jnp.clip(jnp.rint(jnp.cos(ang) * 128), -127, 127).astype(jnp.int32)
    s = jnp.clip(jnp.rint(jnp.sin(ang) * 128), -127, 127).astype(jnp.int32)
    x = jnp.asarray(x_i8, jnp.int32)
    x1, x2 = x[..., :half], x[..., half:]
    y = jnp.concatenate([(x1 * c - x2 * s + 64) >> 7, (x1 * s + x2 * c + 64) >> 7], -1)
    return jnp.clip(y, -128, 127).astype(jnp.int8)


def isilu(x_i8, s_in: float, s_out: float):
    """Integer SiLU through the ITAMax exp2 table."""
    v = requantize_wide(x_i8, *qparams(s_in, 1.0, ITAMAX_LOGIT_SCALE), out_bits=14)
    e = exp2_int(jnp.clip(jnp.abs(v), 0, 1 << 13), EXP_LUT8)
    denom = 256 + e
    sig = jnp.where(v >= 0, (256 * 256) // denom, (256 * e) // denom)
    return requantize(jnp.asarray(x_i8, jnp.int32) * sig, *qparams(s_in, 1.0 / 256.0, s_out))


def _igelu_consts(s: float):
    s_erf = s / math.sqrt(2.0)
    s_l = -0.2888 * s_erf * s_erf
    return (int(math.floor(-1.769 / s_erf)), int(math.floor(1.0 / s_l)),
            int(math.floor(1.0 / s_l)), s * (-s_l) / 2.0)


def igelu(q_i8, s_pre: float, s_out: float):
    """I-BERT integer GeLU of an int8 pre-activation, requantized to int8."""
    q_b, q_c, q_1, out_scale = _igelu_consts(s_pre)
    q = jnp.asarray(q_i8, jnp.int32)
    q_abs = jnp.minimum(jnp.abs(q), -q_b)
    q_erf = jnp.sign(q) * ((q_abs + q_b) * (q_abs + q_b) + q_c)
    return requantize(-(q * (q_erf + q_1)), *qparams(out_scale, 1.0, s_out))


def linear(x_i8, w_i8, s_in: float, s_w: float, s_out: float, bias=None,
           gelu_pre: float | None = None):
    """int8 GEMM with int32 accumulation, requantized (optionally through
    i-GeLU at the pre-activation grid ``gelu_pre``)."""
    acc = jnp.matmul(x_i8, w_i8, preferred_element_type=jnp.int32)
    if bias is not None:
        acc = acc + jnp.asarray(bias, jnp.int32)
    if gelu_pre is None:
        return requantize(acc, *np_quantize_multiplier(s_in * s_w / s_out))
    pre = requantize(acc, *np_quantize_multiplier(s_in * s_w / gelu_pre))
    return igelu(pre, gelu_pre, s_out)
