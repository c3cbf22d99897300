"""Shared model layers — float path + integer (w8a8) counterparts.

Float layers are used for training (optionally with QAT fake-quant) and as
accuracy references.  Integer layers implement the paper's end-to-end
8-bit inference: activations are int8 tensors threaded between ops, with
static python-float scales carried by a :class:`QuantConfig` (the PTQ
product; defaults are used for shape-only dry-runs where values are
irrelevant).

Engine mapping (the paper's heterogeneous split):
  accelerator ("ITA")   : qlinear (GEMM+act), quantized attention
  cluster (fallback)    : norms, residual adds, RoPE, SiLU, router,
                          head-accumulation — integer software kernels
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import ilayernorm as iln
from repro.core import itamax as im
from repro.core.igelu import gelu_f32
from repro.core.quant_linear import (
    ACT_GELU,
    ACT_IDENTITY,
    ACT_RELU,
    QLinearParams,
    make_qlinear_params,
    qlinear_i8,
)
from repro.quant.qparams import make_qparams, requantize, requantize_wide


# ---------------------------------------------------------------------------
# Quantization configuration (static scales; PTQ refines them)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuantConfig:
    """Static per-site activation scales for the integer path.

    Uniform defaults make shape-only dry-runs and scan-over-layers possible
    (one set of multipliers shared by all layers); PTQ on the paper models
    produces calibrated per-site values via ``overrides``.
    """

    s_act: float = 0.05  # generic activation grid
    s_res: float = 0.08  # residual stream grid
    s_w: float = 0.01  # default weight scale for shape-only init
    overrides: tuple = ()  # ((site_name, scale), ...) — kept hashable

    def site(self, name: str, default: float | None = None) -> float:
        for k, v in self.overrides:
            if k == name:
                return v
        return default if default is not None else self.s_act


# ---------------------------------------------------------------------------
# Float layers
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, bias: bool, dtype) -> dict:
    w = jax.random.normal(key, (d_in, d_out), dtype) / math.sqrt(d_in)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(kind: str, d: int, dtype) -> dict:
    if kind == "np_layernorm":
        return {}
    if kind == "rmsnorm":
        return {"g": jnp.ones((d,), dtype)}
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def norm_apply(kind: str, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    if kind == "rmsnorm":
        return iln.rmsnorm_f32(x, p["g"])
    if kind == "np_layernorm":
        return iln.layernorm_f32(x)
    return iln.layernorm_f32(x, p["g"], p["b"])


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float, dtype=jnp.float32):
    """positions [...]; returns cos/sin [..., head_dim//2]."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [B, H, S, D]; cos/sin [S, D/2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, None] if cos.ndim == 2 else cos
    s = sin[None, None] if sin.ndim == 2 else sin
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def silu(x):
    return x * jax.nn.sigmoid(x)


def mask_padded_logits(logits: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """-inf the Megatron-style vocab-padding classes before softmax/CE."""
    if logits.shape[-1] == vocab:
        return logits
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape[-1:], 0)
    neg = jnp.asarray(-1e9, logits.dtype)
    return jnp.where(ids < vocab, logits, neg)


def mlp_forward(p: dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        return (silu(linear(p["gate"], x)) * linear(p["up"], x)) @ p["down"]["w"]
    # gelu MLP
    return gelu_f32(linear(p["up"], x)) @ p["down"]["w"]


def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "gate": init_linear(ks[0], d_model, d_ff, False, dtype),
            "up": init_linear(ks[1], d_model, d_ff, False, dtype),
            "down": init_linear(ks[2], d_ff, d_model, False, dtype),
        }
    return {
        "up": init_linear(ks[0], d_model, d_ff, True, dtype),
        "down": init_linear(ks[1], d_ff, d_model, True, dtype),
    }


# ---------------------------------------------------------------------------
# Integer ("cluster") helpers
# ---------------------------------------------------------------------------

def norm_apply_i8(kind: str, pq: dict, x_q: jnp.ndarray, s_gamma: float, s_out: float):
    if kind == "rmsnorm":
        return iln.irmsnorm_i8(x_q, pq["g_q"], s_gamma, s_out)
    if kind == "np_layernorm":
        return iln.ilayernorm_np_i8(x_q, s_out)
    return iln.ilayernorm_i8(x_q, pq["g_q"], pq["beta_q"], s_gamma, s_out)


def iadd_i8(a_q, b_q, mult_a, shift_a, mult_b, shift_b):
    """Residual add on a common grid: requant each operand, saturating add."""
    a = requantize_wide(a_q, mult_a, shift_a, out_bits=16)
    b = requantize_wide(b_q, mult_b, shift_b, out_bits=16)
    return jnp.clip(a + b, -128, 127).astype(jnp.int8)


def make_iadd_params(s_a: float, s_b: float, s_out: float):
    qa = make_qparams(s_a, 1.0, s_out)
    qb = make_qparams(s_b, 1.0, s_out)
    return (qa.mult, qa.shift, qb.mult, qb.shift)


_ROPE_BITS = 7  # Q0.7 trig tables


def rope_tables_i8(positions: jnp.ndarray, head_dim: int, theta: float):
    cos, sin = rope_cos_sin(positions, head_dim, theta)
    c_q = jnp.clip(jnp.rint(cos * (1 << _ROPE_BITS)), -127, 127).astype(jnp.int32)
    s_q = jnp.clip(jnp.rint(sin * (1 << _ROPE_BITS)), -127, 127).astype(jnp.int32)
    return c_q, s_q


def apply_rope_i8(x_q: jnp.ndarray, c_q: jnp.ndarray, s_q: jnp.ndarray) -> jnp.ndarray:
    """Integer rotary embedding (cluster op): Q0.7 rotation, scale preserved."""
    x = jnp.asarray(x_q, jnp.int32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = c_q[None, None] if c_q.ndim == 2 else c_q
    s = s_q[None, None] if s_q.ndim == 2 else s_q
    r = 1 << (_ROPE_BITS - 1)
    y1 = (x1 * c - x2 * s + r) >> _ROPE_BITS
    y2 = (x1 * s + x2 * c + r) >> _ROPE_BITS
    y = jnp.concatenate([y1, y2], axis=-1)
    return jnp.clip(y, -128, 127).astype(jnp.int8)


def isilu_i8(x_q: jnp.ndarray, s_in: float, s_out: float) -> jnp.ndarray:
    """Integer SiLU (cluster op — ITA's activation unit has no SiLU mode).

    sigma(x) = 2^(x*log2 e) / (1 + 2^(x*log2 e)) evaluated with the ITAMax
    exp2 machinery: requantize x onto the log2 grid, exponentiate with the
    8-bit LUT, one integer division per element.
    """
    qp = make_qparams(s_in, 1.0, im.ITAMAX_LOGIT_SCALE)
    v = requantize_wide(x_q, qp.mult, qp.shift, out_bits=14)  # log-grid value
    t = jnp.clip(jnp.abs(v), 0, 1 << 13)
    # ~256 * e^-|x|; an XLA op, so the table is read by a gather
    e = im._exp2_int(t, im.EXP_LUT, im.EXP_LUT_BITS, lookup=im.lut_gather)
    denom = 256 + e
    sig_pos = (256 * 256) // denom  # x >= 0 branch, Q8 in [128, 256]
    sig_neg = (256 * e) // denom  # x < 0 branch, Q8 in [0, 128]
    sig = jnp.where(v >= 0, sig_pos, sig_neg)
    acc = jnp.asarray(x_q, jnp.int32) * sig  # scale s_in / 256
    qo = make_qparams(s_in, 1.0 / 256.0, s_out)
    return requantize(acc, qo.mult, qo.shift)


def silu_i8_ref_f32(x):
    return silu(x)


# ---------------------------------------------------------------------------
# Quantized linear plumbing (ITA GEMM mode at model level)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QLinearSite:
    """Static description of one quantized linear site."""

    s_in: float
    s_w: float
    s_out: float
    act: int = ACT_IDENTITY
    s_preact: float | None = None

    def params(self) -> QLinearParams:
        return make_qlinear_params(self.s_in, self.s_w, self.s_out, self.act, self.s_preact)


def qlinear(pq: dict, x_q: jnp.ndarray, site: QLinearSite) -> jnp.ndarray:
    return qlinear_i8(x_q, pq["w_q"], pq.get("b_q"), site.params())


def quantize_linear_params(p: dict, s_in: float) -> tuple[dict, float]:
    """Float linear params -> int8 weights (+int32 bias), per-tensor scale."""
    from repro.quant.qparams import quantize_weight_per_tensor

    w_q, s_w = quantize_weight_per_tensor(p["w"])
    s_w = float(s_w)
    out = {"w_q": w_q}
    if "b" in p:
        out["b_q"] = jnp.asarray(jnp.rint(p["b"] / (s_in * s_w)), jnp.int32)
    return out, s_w


def init_qlinear(key, d_in: int, d_out: int, bias: bool) -> dict:
    """Shape-only int8 init (dry-run / synthetic serving)."""
    w_q = jax.random.randint(key, (d_in, d_out), -127, 128, jnp.int8)
    p = {"w_q": w_q}
    if bias:
        p["b_q"] = jnp.zeros((d_out,), jnp.int32)
    return p
