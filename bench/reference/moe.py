"""The plain integer reference of a decoder whose layers are expert layers
(Qwen1.5-MoE-A2.7B's block), and its seeded weights.

``make_codes`` draws every weight as int8 codes from the seed, on the
device, one jitted call a layer: the linear maps, the router and the
shared-expert gate at the spread of a unit-variance init scaled by
``1/sqrt(fan_in)`` on the ``s_w`` grid, the q/k/v biases as int32 codes,
the RMSNorm gains around 1.0 on their 1/64 grid, the embedding at the
code spread the configuration states.  The program is handed the same
codes as they are (an int8 pytree).

The reference runs one whole sequence at a time, with no kernel, plan,
paging, batching or grouping, and computes every expert on every row, one
expert at a time, keeping the part the router gave it:

* RMSNorm: integer, ``(x << 10) // isqrt(mean(x^2))`` times the gain;
* attention: the q/k/v maps with their biases, Q0.7 RoPE at the
  configuration's theta, flash ITAMax over the key blocks the
  configuration states (as ``models.py``);
* the expert layer: int32 router logits; the top-k taken from a stable
  sort of the negated logits (ties to the lowest expert); the weights a
  rowwise ITAMax (Q0.7) over all the logits requantised onto the router
  grid ``s_router``, not renormalised; ``requant(sum_k w_k * y_k)``; the
  shared expert scaled by ``round(256 * sigmoid(code * s_gate))`` of its
  gate's int8 code on ``s_gate``, then added;
* the head: untied, int32 logits times ``s_act * s_w``.

``decoder_gap`` reads a served request as the widest gap by which a served
token's logit lies below the reference's best; ``control_gap`` reads the
control (int4 weights) on the same tokens.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import intops as I
from bench.reference.models import _freeze, _heads, _merge, _request_arrays, _thaw, key_blocks

S_GAMMA = 1.0 / 64.0


def ref_arch(cfg: dict) -> dict:
    """The reference's view of a configuration file: sizes and grids."""
    return dict(cfg["arch"], quant=cfg["quant"])


def _vocab_padded(arch: dict) -> int:
    return (arch["vocab"] + 255) // 256 * 256


def layer_shapes(arch: dict) -> dict:
    """Shapes of one layer's codes (int8; the q/k/v biases int32)."""
    d, h, hkv, hd = arch["d_model"], arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    e, f = arch["n_experts"], arch["d_ff_expert"]
    fs = arch["n_shared_experts"] * f  # the shared experts fused into one SwiGLU
    qkv = (h + 2 * hkv) * hd
    return {"norm1": (d,), "norm2": (d,), "wqkv": (d, qkv), "bqkv": (qkv,), "wo": (h * hd, d),
            "router": (d, e), "experts_gate": (e, d, f), "experts_up": (e, d, f),
            "experts_down": (e, f, d), "shared_gate_proj": (d, fs), "shared_up_proj": (d, fs),
            "shared_down_proj": (fs, d), "shared_expert_gate": (d, 1)}



def _codes(key, shape, sigma: float, centre: float = 0.0, lo: int = -127):
    x = jax.random.normal(key, shape) * sigma + centre
    return jnp.clip(jnp.rint(x), lo, 127).astype(jnp.int8)


def make_codes(arch: dict, weights: dict, seed: int) -> dict:
    """Every weight as codes drawn from ``seed`` on the device:
    ``{"embed", "lm_head", "final_norm", "layers": [one dict a layer]}``."""
    q = arch["quant"]
    shapes = layer_shapes(arch)
    d, vp = arch["d_model"], _vocab_padded(arch)

    def linear_sigma(fan_in: int) -> float:
        return 1.0 / math.sqrt(fan_in) / q["s_w"]

    def draw_layer(key):
        keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
        out = {}
        for name, shape in shapes.items():
            if name.startswith("norm"):
                out[name] = _codes(keys[name], shape, weights["norm_code_std"], 64.0, lo=1)
            elif name == "bqkv":
                std = weights["qkv_bias_std"] / (q["s_act"] * q["s_w"])
                out[name] = jnp.rint(jax.random.normal(keys[name], shape) * std).astype(jnp.int32)
            else:
                out[name] = _codes(keys[name], shape, linear_sigma(shape[-2]))
        return out

    def draw_ends(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"embed": _codes(k1, (vp, d), weights["embed_code_std"]),
                "lm_head": _codes(k2, (d, vp), linear_sigma(d)),
                "final_norm": _codes(k3, (d,), weights["norm_code_std"], 64.0, lo=1)}

    # the seed may exceed 32 bits: fold its high word into the key
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    k_ends, k_layers = jax.random.split(key)
    out = jax.jit(draw_ends)(k_ends)
    layer = jax.jit(draw_layer)
    out["layers"] = [layer(k) for k in jax.random.split(k_layers, arch["n_layers"])]
    return out


def int4_codes(codes: dict) -> dict:
    """The control's weights: each int8 tensor rounded to 4 bits (per-tensor
    absmax scale), then written back on the int8 grid; int32 biases kept."""
    def four(c):
        if c.dtype != jnp.int8:
            return c
        s = jnp.maximum(jnp.max(jnp.abs(c.astype(jnp.float32))), 1.0) / 7.0
        return jnp.clip(jnp.rint(jnp.clip(jnp.rint(c / s), -7, 7) * s), -127, 127).astype(jnp.int8)

    return jax.tree.map(jax.jit(four), codes)


def sigmoid_table(s_gate: float) -> np.ndarray:
    """``round(256 * sigmoid(c * s_gate))`` for the int8 codes c = -128..127."""
    c = np.arange(-128, 128, dtype=np.float64) * s_gate
    return np.rint(256.0 / (1.0 + np.exp(-c))).astype(np.int32)


def rmsnorm(x_i8, gamma, s_out: float):
    x = jnp.asarray(x_i8, jnp.int32)
    sigma = I.isqrt(jnp.sum(x * x, axis=-1, keepdims=True) // x.shape[-1])
    acc = ((x << I.NORM_BITS) // sigma) * jnp.asarray(gamma, jnp.int32)
    return I.requantize(acc, *I.qparams(I.NORM_SCALE, S_GAMMA, s_out))


def itamax_weights(codes, shift: int):
    """Rowwise ITAMax (Q0.7) of int8 codes whose step is 2^shift ITAMax steps."""
    x = jnp.asarray(codes, jnp.int32) << shift
    t = jnp.clip(jnp.max(x, axis=-1, keepdims=True) - x, 0, 1 << 20)
    val = I.exp2_int(t, I.EXP_LUT8)
    d = jnp.maximum(jnp.sum(val, axis=-1, keepdims=True), 1)
    inv = ((1 << 23) + (d >> 1)) // d
    return jnp.clip(I.rshift_round(val * inv, 23 - 7), 0, 127)


def expert_layer(arch: dict, lw: dict, hn):
    """The expert layer over rows ``hn`` int8 [T, D]: every expert on every
    row, each keeping the weight the router gave it (0 if not chosen)."""
    q = arch["quant"]
    a, w, k = q["s_act"], q["s_w"], arch["top_k"]
    logits = jnp.matmul(hn, lw["router"], preferred_element_type=jnp.int32)  # [T, E]
    chosen = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    shift = round(math.log2(q["s_router"] / I.ITAMAX_LOGIT_SCALE))
    probs = itamax_weights(I.requantize(logits, *I.qparams(a, w, q["s_router"])), shift)
    picked = (jnp.arange(logits.shape[-1])[None, :, None] == chosen[:, None, :]).any(-1)
    gate_w = jnp.where(picked, probs, 0)  # [T, E]

    def swiglu(x, wg, wu, wd):
        g = I.linear(x, wg, a, w, a)
        sg = I.isilu(g, a, a)
        prod = jnp.asarray(sg, jnp.int32) * jnp.asarray(I.linear(x, wu, a, w, a), jnp.int32)
        return I.linear(I.requantize(prod, *I.qparams(a, a, a)), wd, a, w, a)

    def one_expert(acc, ex):
        wg, wu, wd, col = ex
        return acc + col[:, None] * jnp.asarray(swiglu(hn, wg, wu, wd), jnp.int32), None

    acc0 = jnp.zeros(hn.shape, jnp.int32)
    acc, _ = jax.lax.scan(one_expert, acc0, (lw["experts_gate"], lw["experts_up"],
                                             lw["experts_down"], gate_w.T))
    routed = I.requantize(acc, *I.qparams(a, 2.0 ** -7, a))
    sh = swiglu(hn, lw["shared_gate_proj"], lw["shared_up_proj"], lw["shared_down_proj"])
    code = I.linear(hn, lw["shared_expert_gate"], a, w, q["s_gate"])
    sig = jnp.asarray(sigmoid_table(q["s_gate"]))[jnp.asarray(code, jnp.int32) + 128]
    gated = I.requantize(jnp.asarray(sh, jnp.int32) * sig, *I.qparams(a, 2.0 ** -8, a))
    return I.iadd(routed, gated, a, a, a)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(arch_key, serve_key, lw, x, n_prompt):
    """One decoder layer over one request's rows x int8 [1, R, D]."""
    arch, serve = _thaw(arch_key), _thaw(serve_key)
    q = arch["quant"]
    a, r, w = q["s_act"], q["s_res"], q["s_w"]
    h, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    _, blk_p, blk_g = key_blocks(arch, serve)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    hn = rmsnorm(x, lw["norm1"], a)
    qkv = I.linear(hn, lw["wqkv"], a, w, a, bias=lw["bqkv"])
    qh = I.rope(_heads(qkv[..., : h * hd], h, hd), pos, arch["rope_theta"])
    kh = I.rope(_heads(qkv[..., h * hd:(h + hkv) * hd], hkv, hd), pos, arch["rope_theta"])
    vh = _heads(qkv[..., (h + hkv) * hd:], hkv, hd)
    att_p = I.attention(qh, kh, vh, pos, blk_p, a, hd)
    att_g = I.attention(qh, kh, vh, pos, blk_g, a, hd)
    att = jnp.where((pos < n_prompt)[None, None, :, None], att_p, att_g)
    x = I.iadd(x, I.linear(_merge(att), lw["wo"], a, w, a), r, a, r)
    hn = rmsnorm(x, lw["norm2"], a)
    return I.iadd(x, expert_layer(arch, lw, hn[0])[None], r, a, r)


@functools.partial(jax.jit, static_argnums=0)
def _head(arch_key, ends, x):
    arch = _thaw(arch_key)
    q = arch["quant"]
    hn = rmsnorm(x[0], ends["final_norm"], q["s_act"])
    acc = jnp.matmul(hn, ends["lm_head"], preferred_element_type=jnp.int32)
    return acc[:, : arch["vocab"]].astype(jnp.float32) * (q["s_act"] * q["s_w"])


def _logits(arch: dict, serve: dict, codes: dict, tokens, n_prompt):
    """Logits of every row of one request [1, R] (float32 [R, vocab])."""
    ak, sk = _freeze(arch), _freeze(serve)
    x = codes["embed"][tokens]
    for lw in codes["layers"]:
        x = _layer(ak, sk, lw, x, n_prompt)
    return _head(ak, {k: codes[k] for k in ("final_norm", "lm_head")}, x)


def _gaps(ref, targets):
    at = jnp.take_along_axis(ref, jnp.asarray(targets[0])[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - at


def decoder_gap(arch: dict, serve: dict, codes: dict, prompt, served) -> float:
    """Widest logit gap by which a served token lies below the reference's
    best logit at its position (0 where every served token is greedy)."""
    tokens, targets, n, rows = _request_arrays(arch, serve, prompt, served)
    gap = _gaps(_logits(arch, serve, codes, tokens, n), targets)
    return float(np.max(np.asarray(gap)[rows]))


def control_gap(arch: dict, serve: dict, codes: dict, control_codes: dict,
                prompt, served) -> float:
    """The control's reading on the same prompt and served tokens: at each
    position the token that ``control_codes`` put first, read by its gap
    under the reference."""
    tokens, _, n, rows = _request_arrays(arch, serve, prompt, served)
    picks = jnp.argmax(_logits(arch, serve, control_codes, tokens, n), axis=-1)[None]
    gap = _gaps(_logits(arch, serve, codes, tokens, n), picks)
    return float(np.max(np.asarray(gap)[rows]))

