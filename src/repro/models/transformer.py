"""Dense decoder-only transformer LM (GQA), float + w8a8 integer paths.

Covers qwen1.5-110b, mistral-large-123b, stablelm-1.6b, olmo-1b and the
llava-next-34b backbone.  Layers are stacked on a leading axis and run
under ``lax.scan`` (keeps HLO size O(1) in depth — essential for the
80-layer dry-run compiles).

Integer path: end-to-end int8 per the paper — int8 embedding table, integer
norms ("cluster"), int8 QKV/O/MLP GEMMs ("ITA"), fused quantized attention
with streaming ITAMax, integer RoPE/SiLU/residual ("cluster"), float
logits only at the LM head output.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.attention import (
    MhaQParams,
    attention_f32,
    attention_flash_i8,
)
from repro.core.quant_linear import ACT_IDENTITY
from repro.models import layers as L
from repro.quant.qparams import make_qparams, requantize


# ---------------------------------------------------------------------------
# Float parameters
# ---------------------------------------------------------------------------

def _qkv_dims(cfg: ArchConfig) -> int:
    return (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim


def init_layer(cfg: ArchConfig, key, dtype) -> dict:
    from repro.models import moe as moe_mod

    ks = jax.random.split(key, 4)
    if cfg.n_experts:
        mlp = moe_mod.init_moe_layer(cfg, ks[2], dtype)
    else:
        mlp = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return {
        "norm1": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "attn": {
            "wqkv": L.init_linear(ks[0], cfg.d_model, _qkv_dims(cfg), cfg.qkv_bias, dtype),
            "wo": L.init_linear(ks[1], cfg.n_heads * cfg.head_dim, cfg.d_model, False, dtype),
        },
        "norm2": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "mlp": mlp,
    }


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(cfg, k, dtype))(layer_keys)
    params = {
        "embed": {"table": jax.random.normal(ks[1], (cfg.vocab_padded, cfg.d_model), dtype) * 0.02},
        "layers": layers,
        "final_norm": L.init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(ks[2], cfg.d_model, cfg.vocab_padded, False, dtype)
    return params


# ---------------------------------------------------------------------------
# Float forward / prefill / decode
# ---------------------------------------------------------------------------

def _split_heads(qkv: jnp.ndarray, cfg: ArchConfig):
    b, s, _ = qkv.shape
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = jnp.split(qkv, [h * d, (h + hkv) * d], axis=-1)
    q = q.reshape(b, s, h, d).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, d).transpose(0, 2, 1, 3)
    return q, k, v


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


_CHUNKED_ATTN_MIN_SEQ = 2048  # float train path: flash-chunked beyond this


def attn_block(cfg: ArchConfig, lp: dict, x: jnp.ndarray, positions, *, qat=False, causal=True):
    from repro.core.attention import attention_f32_chunked
    from repro.runtime.activations import constrain

    h = L.norm_apply(cfg.norm, lp["norm1"], x)
    h = constrain(h, "gathered")  # Megatron-SP boundary: keep TP weights sharded
    if qat:
        # QAT: inject the int8 weight grid (STE) on the projections
        from repro.quant.fake_quant import fake_quant_weight

        lp = {
            "attn": {
                "wqkv": {**lp["attn"]["wqkv"], "w": fake_quant_weight(lp["attn"]["wqkv"]["w"])},
                "wo": {**lp["attn"]["wo"], "w": fake_quant_weight(lp["attn"]["wo"]["w"])},
            },
            "norm1": lp["norm1"],
            "norm2": lp["norm2"],
            "mlp": lp["mlp"],
        }
    qkv = L.linear(lp["attn"]["wqkv"], h)
    q, k, v = _split_heads(qkv, cfg)
    q = constrain(q, "heads")  # attention internals are head-parallel
    if cfg.rope:
        cos, sin = L.rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, x.dtype)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    clip = None
    if qat:
        from repro.core.itamax import ITAMAX_LOGIT_SCALE

        clip = 127 * ITAMAX_LOGIT_SCALE
    if q.shape[2] >= _CHUNKED_ATTN_MIN_SEQ:
        out = attention_f32_chunked(q, k, v, causal=causal, logit_clip=clip)
    else:
        out = attention_f32(q, k, v, causal=causal, logit_clip=clip)
    out = constrain(out, "heads")
    return x + L.linear(lp["attn"]["wo"], _merge_heads(out))


def mlp_block(cfg: ArchConfig, lp: dict, x: jnp.ndarray):
    """Returns (x, aux_loss) — aux is the MoE load-balance term (0 if dense)."""
    from repro.runtime.activations import constrain

    h = L.norm_apply(cfg.norm, lp["norm2"], x)
    h = constrain(h, "gathered")
    if cfg.n_experts:
        from repro.models import moe as moe_mod

        out, aux = moe_mod.moe_ffn(cfg, lp["mlp"], h)
        return x + out, aux
    return x + L.mlp_forward(lp["mlp"], h, cfg.mlp), jnp.zeros((), jnp.float32)


def layer_fwd(cfg: ArchConfig, lp: dict, x: jnp.ndarray, positions, *, qat=False, causal=True):
    x = attn_block(cfg, lp, x, positions, qat=qat, causal=causal)
    return mlp_block(cfg, lp, x)


def embed_input(cfg: ArchConfig, params: dict, batch: dict) -> jnp.ndarray:
    x = params["embed"]["table"][batch["tokens"]]
    if cfg.family == "vlm" and "patches" in batch:
        # anyres stub: precomputed patch embeddings prepended to the text
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    return x


def lm_head(cfg: ArchConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    x = L.norm_apply(cfg.norm, params["final_norm"], x)
    if cfg.tie_embeddings:
        return x @ params["embed"]["table"].T
    return x @ params["lm_head"]["w"]


def forward(
    cfg: ArchConfig,
    params: dict,
    batch: dict,
    *,
    qat: bool = False,
    return_aux: bool = False,
    remat: bool = False,
):
    """Causal LM forward. Returns logits [B, S(+patches), V] (+ MoE aux)."""
    from repro.runtime.activations import constrain

    x = embed_input(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s)

    def body(x, lp):
        x = constrain(x, "residual")
        x, aux = layer_fwd(cfg, lp, x, positions, qat=qat)
        return constrain(x, "residual"), aux

    if remat:
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    logits = lm_head(cfg, params, x)
    if return_aux:
        return logits, jnp.sum(auxs)
    return logits


MOE_AUX_WEIGHT = 0.01


def loss_fn(
    cfg: ArchConfig, params: dict, batch: dict, *, qat: bool = False, remat: bool = False
) -> jnp.ndarray:
    logits, aux = forward(cfg, params, batch, qat=qat, return_aux=True, remat=remat)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # vlm: drop patch positions
        logits = logits[:, -labels.shape[1] :]
    logits = L.mask_padded_logits(logits, cfg.vocab)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean() + MOE_AUX_WEIGHT * aux


# -- float KV cache serving ---------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.float32) -> dict:
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ArchConfig, params: dict, batch: dict, max_len: int):
    """Float prefill: forward + cache capture. Returns (logits, cache)."""
    x = embed_input(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)

    def body(x, lp):
        h = L.norm_apply(cfg.norm, lp["norm1"], x)
        qkv = L.linear(lp["attn"]["wqkv"], h)
        q, k, v = _split_heads(qkv, cfg)
        if cfg.rope:
            cos, sin = L.rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, x.dtype)
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        out = attention_f32(q, k, v, causal=True)
        x = x + L.linear(lp["attn"]["wo"], _merge_heads(out))
        x, _ = mlp_block(cfg, lp, x)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    cache = init_cache(cfg, b, max_len, x.dtype)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    cache["len"] = jnp.asarray(s, jnp.int32)
    logits = lm_head(cfg, params, x[:, -1:])
    return logits, cache


def decode_step(cfg: ArchConfig, params: dict, cache: dict, token: jnp.ndarray):
    """One-token float decode. token [B,1] int32. Returns (logits, cache)."""
    x = params["embed"]["table"][token]
    pos = cache["len"]
    positions = pos[None] if pos.ndim == 0 else pos
    b = x.shape[0]
    smax = cache["k"].shape[3]
    kj = jnp.arange(smax)

    def body(x, xs):
        lp, kc, vc = xs
        h = L.norm_apply(cfg.norm, lp["norm1"], x)
        qkv = L.linear(lp["attn"]["wqkv"], h)
        q, k, v = _split_heads(qkv, cfg)
        if cfg.rope:
            cos, sin = L.rope_cos_sin(jnp.asarray([pos]), cfg.head_dim, cfg.rope_theta, x.dtype)
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))
        mask = (kj <= pos)[None, None, None, :]
        out = attention_f32(q, kc, vc, mask=mask)
        x = x + L.linear(lp["attn"]["wo"], _merge_heads(out))
        x, _ = mlp_block(cfg, lp, x)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
    return lm_head(cfg, params, x), new_cache


# ---------------------------------------------------------------------------
# Integer (w8a8) parameters + forward
# ---------------------------------------------------------------------------

def init_qlayer(cfg: ArchConfig, key) -> dict:
    from repro.models import moe as moe_mod

    ks = jax.random.split(key, 5)

    def qnorm():
        if cfg.norm == "np_layernorm":
            return {}
        p = {"g_q": jnp.full((cfg.d_model,), 64, jnp.int8)}
        if cfg.norm == "layernorm":
            p["beta_q"] = jnp.zeros((cfg.d_model,), jnp.int32)
        return p

    lp = {
        "norm1": qnorm(),
        "attn": {
            "wqkv": L.init_qlinear(ks[0], cfg.d_model, _qkv_dims(cfg), cfg.qkv_bias),
            "wo": L.init_qlinear(ks[1], cfg.n_heads * cfg.head_dim, cfg.d_model, False),
        },
        "norm2": qnorm(),
    }
    if cfg.n_experts:
        lp["mlp"] = moe_mod.init_qmoe_layer(cfg, ks[2])
    elif cfg.mlp == "swiglu":
        lp["mlp"] = {
            "gate": L.init_qlinear(ks[2], cfg.d_model, cfg.d_ff, False),
            "up": L.init_qlinear(ks[3], cfg.d_model, cfg.d_ff, False),
            "down": L.init_qlinear(ks[4], cfg.d_ff, cfg.d_model, False),
        }
    else:
        lp["mlp"] = {
            "up": L.init_qlinear(ks[2], cfg.d_model, cfg.d_ff, True),
            "down": L.init_qlinear(ks[3], cfg.d_ff, cfg.d_model, True),
        }
    return lp


def init_qparams(cfg: ArchConfig, key) -> dict:
    """Shape-only integer model (dry-run / synthetic serving)."""
    ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_qlayer(cfg, k))(layer_keys)
    qp = {
        "embed": {"table_q": jax.random.randint(ks[1], (cfg.vocab_padded, cfg.d_model), -127, 128, jnp.int8)},
        "layers": layers,
        "final_norm": {"g_q": jnp.full((cfg.d_model,), 64, jnp.int8)}
        if cfg.norm != "np_layernorm"
        else {},
    }
    if cfg.norm == "layernorm":
        qp["final_norm"]["beta_q"] = jnp.zeros((cfg.d_model,), jnp.int32)
    if not cfg.tie_embeddings:
        qp["lm_head"] = L.init_qlinear(ks[2], cfg.d_model, cfg.vocab_padded, False)
    return qp


_S_GAMMA = 1.0 / 64.0  # shape-only norm gain grid (g_q=64 -> gamma=1.0)


def _sites(cfg: ArchConfig, q: L.QuantConfig):
    """Static quantized-site table shared by all layers."""
    a, r, w = q.s_act, q.s_res, q.s_w
    mk = L.QLinearSite
    return {
        "wqkv": mk(a, w, a),
        "wo": mk(a, w, a),
        "gate": mk(a, w, a),
        "up": mk(a, w, a),
        "down": mk(a, w, a),
        "mha": MhaQParams.make_flash(a, a, a, a, max(cfg.head_dim, 1)),
        "res_attn": L.make_iadd_params(r, a, r),
        "res_mlp": L.make_iadd_params(r, a, r),
        "silu_prod": make_qparams(a, a, a),
    }


def qlayer_fwd(
    cfg: ArchConfig,
    lp: dict,
    x_q: jnp.ndarray,
    positions,
    q: L.QuantConfig,
    *,
    causal: bool = True,
    kv_override=None,
    kv_len=None,
    block_k: int = 512,
):
    """One integer transformer layer. x_q int8 [B,S,D] on the s_res grid.

    ``kv_override`` may swap in larger K/V tensors (the decode path returns
    the full KV cache); ``kv_len`` then masks the unwritten tail inside the
    flash attention.  Prefill, full forward and single-token decode all run
    THIS function — one source of truth for the integer arithmetic.
    """
    st = _sites(cfg, q)
    h_q = L.norm_apply_i8(cfg.norm, lp["norm1"], x_q, _S_GAMMA, q.s_act)
    qkv = L.qlinear(lp["attn"]["wqkv"], h_q, st["wqkv"])
    qh, kh, vh = _split_heads(qkv, cfg)
    if cfg.rope:
        c_q, s_q = L.rope_tables_i8(positions, cfg.head_dim, cfg.rope_theta)
        qh = L.apply_rope_i8(qh, c_q, s_q)
        kh = L.apply_rope_i8(kh, c_q, s_q)
    if kv_override is not None:
        kh, vh = kv_override(kh, vh)
    bk = min(block_k, kh.shape[2])
    out = attention_flash_i8(qh, kh, vh, st["mha"], causal=causal, block_k=bk,
                             kv_len=kv_len)
    out = L.qlinear(lp["attn"]["wo"], _merge_heads(out), st["wo"])
    x_q = L.iadd_i8(x_q, out, *st["res_attn"])

    h_q = L.norm_apply_i8(cfg.norm, lp["norm2"], x_q, _S_GAMMA, q.s_act)
    if cfg.n_experts:
        from repro.models import moe as moe_mod

        m = moe_mod.moe_ffn_w8a8(cfg, lp["mlp"], h_q, q)
    elif cfg.mlp == "swiglu":
        g = L.qlinear(lp["mlp"]["gate"], h_q, st["gate"])
        u = L.qlinear(lp["mlp"]["up"], h_q, st["up"])
        sg = L.isilu_i8(g, q.s_act, q.s_act)
        prod = jnp.asarray(sg, jnp.int32) * jnp.asarray(u, jnp.int32)
        # prod scale = s_act * s_act -> back to the s_act grid
        pq = st["silu_prod"]
        h2 = requantize(prod, pq.mult, pq.shift)
        m = L.qlinear(lp["mlp"]["down"], h2, st["down"])
    else:
        pre = L.qlinear(
            lp["mlp"]["up"],
            h_q,
            L.QLinearSite(q.s_act, q.s_w, q.s_act, act=2, s_preact=q.s_act),
        )
        m = L.qlinear(lp["mlp"]["down"], pre, st["down"])
    return L.iadd_i8(x_q, m, *st["res_mlp"])


def embed_input_w8a8(cfg: ArchConfig, qp: dict, batch: dict) -> jnp.ndarray:
    x_q = qp["embed"]["table_q"][batch["tokens"]]
    if cfg.family == "vlm" and "patches" in batch:
        # frontend stub delivers pre-quantized int8 patch embeddings
        x_q = jnp.concatenate([batch["patches"].astype(jnp.int8), x_q], axis=1)
    return x_q


def lm_head_w8a8(cfg: ArchConfig, qp: dict, x_q: jnp.ndarray, q: L.QuantConfig):
    h_q = L.norm_apply_i8(cfg.norm, qp["final_norm"], x_q, _S_GAMMA, q.s_act)
    w_q = qp["embed"]["table_q"].T if cfg.tie_embeddings else qp["lm_head"]["w_q"]
    acc = jnp.matmul(h_q, w_q, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (q.s_act * q.s_w)  # dequantized logits


def forward_w8a8(
    cfg: ArchConfig, qp: dict, batch: dict, q: L.QuantConfig = L.QuantConfig()
) -> jnp.ndarray:
    x_q = embed_input_w8a8(cfg, qp, batch)
    positions = jnp.arange(x_q.shape[1])

    def body(x, lp):
        return qlayer_fwd(cfg, lp, x, positions, q), None

    x_q, _ = jax.lax.scan(body, x_q, qp["layers"])
    return lm_head_w8a8(cfg, qp, x_q, q)


# -- int8 KV-cache serving ----------------------------------------------------

def init_cache_w8a8(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "len": jnp.zeros((), jnp.int32),
    }


def prefill_w8a8(
    cfg: ArchConfig,
    qp: dict,
    batch: dict,
    max_len: int,
    q: L.QuantConfig = L.QuantConfig(),
    block_k: int = 512,
):
    x_q = embed_input_w8a8(cfg, qp, batch)
    b, s, _ = x_q.shape
    positions = jnp.arange(s)

    def body(x, lp):
        captured = {}

        def grab(kh, vh):
            captured["k"], captured["v"] = kh, vh
            return kh, vh

        x = qlayer_fwd(cfg, lp, x, positions, q, causal=True, kv_override=grab, block_k=block_k)
        return x, (captured["k"], captured["v"])

    x_q, (ks, vs) = jax.lax.scan(body, x_q, qp["layers"])
    cache = init_cache_w8a8(cfg, b, max_len)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    cache["len"] = jnp.asarray(s, jnp.int32)
    return lm_head_w8a8(cfg, qp, x_q[:, -1:], q), cache


def decode_step_w8a8(
    cfg: ArchConfig,
    qp: dict,
    cache: dict,
    token: jnp.ndarray,
    q: L.QuantConfig = L.QuantConfig(),
    block_k: int = 2048,
):
    """One-token decode against the int8 KV cache.

    Runs the SAME ``qlayer_fwd`` integer path as prefill (so the two paths
    cannot drift): the KV override appends this step's K/V to the cache and
    returns the full cache tensors, and ``kv_len`` masks the unwritten tail
    inside the flash attention — bit-identical to attending only the first
    ``pos + 1`` cache rows.
    """
    x_q = qp["embed"]["table_q"][token]
    pos = cache["len"]
    b = x_q.shape[0]

    def body(x, xs):
        lp, kc, vc = xs
        written = {}

        def append(kh, vh):
            written["k"] = jax.lax.dynamic_update_slice(kc, kh, (0, 0, pos, 0))
            written["v"] = jax.lax.dynamic_update_slice(vc, vh, (0, 0, pos, 0))
            return written["k"], written["v"]

        x = qlayer_fwd(
            cfg, lp, x, jnp.asarray([pos]), q, causal=False, kv_override=append,
            kv_len=jnp.full((b, 1, 1, 1), pos + 1, jnp.int32), block_k=block_k,
        )
        return x, (written["k"], written["v"])

    x_q, (ks, vs) = jax.lax.scan(body, x_q, (qp["layers"], cache["k"], cache["v"]))
    new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
    return lm_head_w8a8(cfg, qp, x_q, q), new_cache


# ---------------------------------------------------------------------------
# PTQ: float params -> integer params (uniform static scales)
# ---------------------------------------------------------------------------

def quantize_params(cfg: ArchConfig, params: dict, q: L.QuantConfig = L.QuantConfig()) -> dict:
    """Per-tensor symmetric weight quantization onto the w8a8 layout.

    Weight scales are snapped to the shared static ``q.s_w`` grid (the
    uniform-scale scheme that keeps scan-over-layers homogeneous); PTQ with
    calibration for the paper models refines activations via
    ``QuantConfig.overrides``.
    """

    def quant_w(w):
        return jnp.clip(jnp.rint(w / q.s_w), -127, 127).astype(jnp.int8)

    def quant_linear(p, s_in):
        out = {"w_q": quant_w(p["w"])}
        if "b" in p:
            out["b_q"] = jnp.asarray(jnp.rint(p["b"] / (s_in * q.s_w)), jnp.int32)
        return out

    def quant_norm(p):
        if not p:
            return {}
        out = {"g_q": jnp.clip(jnp.rint(p["g"] / _S_GAMMA), -127, 127).astype(jnp.int8)}
        if "b" in p:
            import repro.core.ilayernorm as iln

            out["beta_q"] = jnp.asarray(
                jnp.rint(p["b"] / (iln.NORM_SCALE * _S_GAMMA)), jnp.int32
            )
        return out

    def quant_layer(lp):
        out = {
            "norm1": quant_norm(lp["norm1"]),
            "attn": {
                "wqkv": quant_linear(lp["attn"]["wqkv"], q.s_act),
                "wo": quant_linear(lp["attn"]["wo"], q.s_act),
            },
            "norm2": quant_norm(lp["norm2"]),
        }
        if cfg.n_experts:
            from repro.models import moe as moe_mod

            out["mlp"] = moe_mod.quantize_moe_layer(cfg, lp["mlp"], quant_w)
        else:
            out["mlp"] = {k: quant_linear(v, q.s_act) for k, v in lp["mlp"].items()}
        return out

    qp = {
        "embed": {
            "table_q": jnp.clip(
                jnp.rint(params["embed"]["table"] / q.s_res), -127, 127
            ).astype(jnp.int8)
        },
        "layers": jax.vmap(quant_layer)(params["layers"]),
        "final_norm": quant_norm(params["final_norm"]),
    }
    if not cfg.tie_embeddings:
        qp["lm_head"] = quant_linear(params["lm_head"], q.s_act)
    return qp
