"""ITAMax — ITA's streaming integer softmax, adapted for TPU.

The paper's ITAMax unit computes Softmax over int8 logits in three stages:

* **DA (Denominator Accumulation)** — while ``Q @ K^T`` results stream out
  of the dot-product units, track the running row maximum and accumulate
  the Softmax denominator; when the maximum changes, renormalize the
  partial sum.
* **DI (Denominator Inversion)** — once a row is complete, invert the
  accumulated denominator (one division per row).
* **EN (Element Normalization)** — when the post-Softmax activations are
  consumed by the ``A @ V`` matmul, normalize the stored logits on the fly
  to produce 8-bit attention weights ``A``.

Arithmetic (documented in DESIGN.md §2): the requantization scale of the
``Q @ K^T`` logits is constrained so that ``log2(e) * S_logit = 2^-B`` with
``B = 5`` fractional bits.  Then for a row with maximum ``m``::

    exp(real_i - real_m) = 2^-((m - q_i) / 2^B)
                         = EXP_LUT[(m - q_i) & (2^B - 1)] >> ((m - q_i) >> B)

with a 32-entry lookup table.  A maximum update by ``d`` renormalizes the
partial denominator with the same LUT (fixed-point multiply + shift) —
this is the TPU-friendly restatement of ITA's shift-based renormalization.

Two execution styles:

* :func:`itamax_rowwise` — the **paper-faithful** two-pass dataflow
  (ITA buffers the int8 logits of a full row, row length <= 512 in the
  ASIC): materializes 8-bit attention weights ``A`` with scale ``2^-7``.
* :class:`FlashItamaxState` + helpers — the **TPU adaptation** used by the
  fused attention kernel and the long-context paths: single pass over KV
  blocks, un-normalized exponentials are accumulated against ``V`` in
  int32 and the division happens once at the end (exact integer division,
  Q7.7 output).  A magnitude guard rescales the accumulator and the
  denominator together when the denominator grows beyond 2^21, keeping
  everything inside int32 even for 500k-token rows.

Every function here is pure jnp; the Pallas kernels inline the same
helpers, and ``kernels/*/ref.py`` oracles call them directly.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.quant.qparams import rounding_rshift

# Number of fractional bits in the base-2 exponent decomposition.
ITAMAX_B = 5
_FRAC_MASK = (1 << ITAMAX_B) - 1

#: The logit quantization scale ITAMax requires: log2(e) * S = 2^-B.
ITAMAX_LOGIT_SCALE = math.log(2.0) / (1 << ITAMAX_B)  # ~0.021661

# U1.8 LUT used by the paper-faithful rowwise path (matches ITA's internal
# precision; 256 == 2^8 represents 1.0).
EXP_LUT_BITS = 8
EXP_LUT = np.round((1 << EXP_LUT_BITS) * 2.0 ** (-np.arange(32) / 32.0)).astype(np.int32)

# U0.7 LUT used by the flash path so un-normalized exponentials fit int8
# and can feed the MXU directly (127 represents ~1.0).
EXP_LUT7_BITS = 7
EXP_LUT7 = np.minimum(
    np.round((1 << EXP_LUT7_BITS) * 2.0 ** (-np.arange(32) / 32.0)), 127
).astype(np.int32)

# U1.10 LUT used to renormalize the flash-path running sums on a max
# update (higher precision than the value LUT; 1024 represents 1.0).
RENORM_LUT_BITS = 10
RENORM_LUT = np.round(
    (1 << RENORM_LUT_BITS) * 2.0 ** (-np.arange(32) / 32.0)
).astype(np.int32)

# Flash-path magnitude guard: rescale denominator+accumulator by 2^-8 when
# the denominator exceeds this (keeps acc < 2^28 for arbitrary row length).
RESCALE_THRESH = 1 << 21
RESCALE_BITS = 8

# DI stage fixed-point width for the rowwise path: inv = round(2^23 / D).
INV_BITS = 23
# Rowwise A output is 7-bit (scale 2^-7): A = (val * inv) >> (INV_BITS - 7).
A_BITS = 7
A_SCALE = 2.0 ** (-A_BITS)


def lut_lookup(table: np.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """``table[r]`` for a 32-entry int32 table and int32 ``r`` in [0, 32).

    A binary tree of selects on the five bits of ``r`` with the entries
    as scalar constants: the TPU kernel compiler has no 1-D gather, and
    Pallas kernels cannot capture array constants.  The XLA reference
    paths call the same function, so kernels and references agree bit
    for bit.
    """
    assert table.shape == (1 << ITAMAX_B,), table.shape
    vals = [np.int32(v) for v in table]
    for bit in range(ITAMAX_B):
        take_hi = ((r >> bit) & 1) == 1
        vals = [jnp.where(take_hi, hi, lo) for lo, hi in zip(vals[0::2], vals[1::2])]
    return jnp.asarray(vals[0], jnp.int32)


def lut_gather(table: np.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """``table[r]`` as an XLA gather, for code that never runs inside a
    Pallas kernel.  Once XLA fuses :func:`lut_lookup`'s select tree into
    the decoder MLP, the TPU compiler spends minutes on the fusion (about
    220 s for one olmo-1b layer at batch 4, against 45 s with the gather)."""
    return jnp.asarray(table, jnp.int32)[r]


def _exp2_int(t: jnp.ndarray, lut: np.ndarray, lut_bits: int,
              lookup=lut_lookup) -> jnp.ndarray:
    """``round(2^lut_bits * 2^(-t / 2^B))`` for non-negative int32 ``t``.

    The integer-part shift uses round-half-up (not floor): small
    exponentials would otherwise be systematically under-weighted and the
    attention rows would sum to < 1.  ``lookup`` reads the table; both
    lookups return exactly ``lut[r]``.
    """
    t = jnp.asarray(t, jnp.int32)
    q = jnp.minimum(t >> ITAMAX_B, 31)
    r = t & _FRAC_MASK
    bias = jnp.where(q > 0, jnp.int32(1) << jnp.maximum(q - 1, 0), 0)
    return (lookup(lut, r) + bias) >> q


def itamax_rowwise(
    logits: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Paper-faithful ITAMax over the last axis of int8 ``logits``.

    Returns int8 attention weights ``A`` in [0, 127] with scale ``2^-7``.
    ``mask`` (bool, True = keep) excludes positions from both max and sum.
    Row length should be <= 2^15 so that the denominator fits INV_BITS.
    """
    x = jnp.asarray(logits, jnp.int32)
    neg = jnp.int32(-(1 << 20))
    if mask is not None:
        x = jnp.where(mask, x, neg)
    m = jnp.max(x, axis=-1, keepdims=True)
    t = jnp.clip(m - x, 0, (1 << 20))  # masked positions get huge t -> val 0
    val = _exp2_int(t, EXP_LUT, EXP_LUT_BITS)
    if mask is not None:
        val = jnp.where(mask, val, 0)
    d = jnp.sum(val, axis=-1, keepdims=True)
    d = jnp.maximum(d, 1)
    inv = ((jnp.int32(1) << INV_BITS) + (d >> 1)) // d  # DI stage
    a = rounding_rshift(val * inv, INV_BITS - A_BITS)  # EN stage
    return jnp.clip(a, 0, 127).astype(jnp.int8)


def itamax_rowwise_f32(logits_f32: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Float reference of what ITAMax approximates (plain softmax)."""
    x = logits_f32 - jnp.max(logits_f32, axis=axis, keepdims=True)
    e = jnp.exp(x)
    return e / jnp.sum(e, axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# Flash-ITAMax: single-pass blocked form (TPU adaptation).
# ---------------------------------------------------------------------------

class FlashItamaxState(NamedTuple):
    """Carry for one (or a batch of) softmax rows processed block-by-block.

    m:   running max of int8 logits, int32, init -2^15 sentinel
    d:   running (rescaled) denominator, int32
    acc: running (rescaled) un-normalized ``sum_i val_i * V[i, :]``, int32
    """

    m: jnp.ndarray
    d: jnp.ndarray
    acc: jnp.ndarray


M_SENTINEL = -(1 << 15)


def flash_init(row_shape: tuple[int, ...], out_dim: int) -> FlashItamaxState:
    return FlashItamaxState(
        m=jnp.full(row_shape + (1,), M_SENTINEL, jnp.int32),
        d=jnp.zeros(row_shape + (1,), jnp.int32),
        acc=jnp.zeros(row_shape + (out_dim,), jnp.int32),
    )


def _mul_q10(x: jnp.ndarray, mult: jnp.ndarray) -> jnp.ndarray:
    """Exact ``floor((x * mult + 512) / 1024)`` in int32 (mult <= 1024).

    Base-1024 double-word decomposition: ``x = hi*2^10 + lo`` gives
    ``x*mult + 512 = (hi*mult)*2^10 + (lo*mult + 512)`` and the floored
    shift distributes exactly because ``lo*mult + 512 >= 0``.
    """
    x = jnp.asarray(x, jnp.int32)
    mult = jnp.asarray(mult, jnp.int32)
    hi = x >> RENORM_LUT_BITS
    lo = x & ((1 << RENORM_LUT_BITS) - 1)
    b = hi * mult  # |b| <= |x|, no overflow
    c = lo * mult + (1 << (RENORM_LUT_BITS - 1))
    return b + (c >> RENORM_LUT_BITS)


def _renorm_factor_apply(x: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """Multiply int32 ``x`` by ``2^(-delta / 2^B)`` (delta >= 0, broadcast)."""
    q = jnp.minimum(delta >> ITAMAX_B, 31)
    r = delta & _FRAC_MASK
    x_shifted = rounding_rshift_safe(x, q)
    return _mul_q10(x_shifted, lut_lookup(RENORM_LUT, r))


def rounding_rshift_safe(x: jnp.ndarray, shift: jnp.ndarray) -> jnp.ndarray:
    """Round-half-up right shift that tolerates shift == 0..31."""
    x = jnp.asarray(x, jnp.int32)
    shift = jnp.asarray(shift, jnp.int32)
    bias = jnp.where(shift > 0, jnp.int32(1) << jnp.maximum(shift - 1, 0), 0)
    return (x + bias) >> shift


def flash_block_update(
    state: FlashItamaxState,
    logits_block: jnp.ndarray,  # int8/int32 [..., bk]
    v_block: jnp.ndarray,  # int8 [bk, out_dim] (or [..., bk, out_dim])
    mask_block: jnp.ndarray | None = None,
) -> FlashItamaxState:
    """One DA + fused A@V step over a KV block (pure-jnp oracle form).

    The Pallas kernel runs exactly this computation with MXU dots.
    """
    if mask_block is not None and logits_block.dtype == jnp.int8:
        # Mask in the int8 domain (4x less select traffic than int32).
        # Sound & bit-exact: real logits are >= -128, so a masked -128 can
        # never raise the row max; masked exponentials are zeroed below.
        logits_block = jnp.where(mask_block, logits_block, jnp.int8(-128))
        x = jnp.asarray(logits_block, jnp.int32)
    else:
        x = jnp.asarray(logits_block, jnp.int32)
        if mask_block is not None:
            x = jnp.where(mask_block, x, jnp.int32(-(1 << 20)))
    bm = jnp.max(x, axis=-1, keepdims=True)
    new_m = jnp.maximum(state.m, bm)
    delta_old = jnp.clip(new_m - state.m, 0, 1 << 12)
    d_r = _renorm_factor_apply(state.d, delta_old)
    acc_r = _renorm_factor_apply(state.acc, delta_old[..., 0:1])

    t = jnp.clip(new_m - x, 0, 1 << 20)
    val = _exp2_int(t, EXP_LUT7, EXP_LUT7_BITS)  # [..., bk] in [0, 127]
    if mask_block is not None:
        val = jnp.where(mask_block, val, 0)
    d_new = d_r + jnp.sum(val, axis=-1, keepdims=True)

    # int8 x int8 -> int32 is the product the MXU takes (the TPU kernel
    # compiler refuses int32 operands); val <= 127, so the cast is exact
    val8 = val.astype(jnp.int8)
    v = jnp.asarray(v_block).astype(jnp.int8)
    if v.ndim == x.ndim:
        # val: [..., q, bk], v: [..., bk, out_dim] with shared leading dims
        contrib = jnp.einsum(
            "...qk,...kd->...qd", val8, v, preferred_element_type=jnp.int32
        )
    else:  # v shared across rows: [bk, out_dim]
        contrib = jnp.einsum("...k,kd->...d", val8, v, preferred_element_type=jnp.int32)
    acc_new = acc_r + contrib

    # Magnitude guard: keep d (and acc, scaled identically so the final
    # ratio is unchanged) inside int32 for arbitrarily long rows.
    over = d_new > RESCALE_THRESH
    d_out = jnp.where(over, rounding_rshift_safe(d_new, RESCALE_BITS), d_new)
    acc_out = jnp.where(over, rounding_rshift_safe(acc_new, RESCALE_BITS), acc_new)
    return FlashItamaxState(m=new_m, d=d_out, acc=acc_out)


def flash_finalize_q77(state: FlashItamaxState) -> jnp.ndarray:
    """EN + DI for the flash path: exact integer division to Q7.7.

    Returns int32 ``round_floor(acc * 2^7 / d)`` in [-2^14, 2^14]; the real
    attention output is ``q77 * S_V * 2^-7`` and is requantized by the
    caller.
    """
    d = jnp.maximum(state.d, 1)
    r = _floor_div(state.acc, d)
    rem = state.acc - r * d
    frac = _floor_div((rem << A_BITS) + (d >> 1), d)
    return r * (1 << A_BITS) + frac


def _floor_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.floor_divide(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32))


def flash_itamax_reference(
    logits: jnp.ndarray,  # int8 [..., n]
    v: jnp.ndarray,  # int8 [..., n, out_dim]
    block: int,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Blocked single-pass oracle: returns Q7.7 int32 [..., out_dim].

    Bit-exact w.r.t. the fused Pallas kernel run with the same block size.
    """
    n = logits.shape[-1]
    assert n % block == 0, (n, block)
    row_shape = logits.shape[:-1]
    out_dim = v.shape[-1]
    state = flash_init(row_shape, out_dim)
    for i in range(0, n, block):
        lb = logits[..., i : i + block]
        vb = v[..., i : i + block, :]
        mb = None if mask is None else mask[..., i : i + block]
        state = flash_block_update(state, lb, vb, mb)
    return flash_finalize_q77(state)
