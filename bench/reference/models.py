"""Seeded weights and the plain integer reference of each model family.

``make_codes`` draws every weight of a configuration as int8 codes from the
seed, on the device, in one jitted call.  The program is handed the same
weights as float tensors that sit exactly on its quantization grids
(``float_params``), so its post-training quantization recovers the codes
unchanged; the reference uses the codes directly and takes nothing from the
program.

The references run one whole sequence at a time, with no kernels, plan,
paging, batching of requests or cache:

* ``decoder_gap``: the dense w8a8 decoder over a prompt and its served
  tokens, read as the widest gap between a served token's logit and the
  best.  The configuration states where flash ITAMax splits the keys: in
  blocks of ``prompt`` rows for prompt rows, of ``generated`` rows for
  generated ones, both counted from row 0 of the request's key rows.
  ``control_gap`` reads the control (other weights) on the same tokens.
* ``encoder_features``: the w8a8 encoder (bidirectional, flash ITAMax in
  key blocks of ``attention_block`` rows), from token ids or from int8
  feature rows, returning the int8 codes of the final normalised hidden
  states.

Where flash ITAMax splits the keys is part of the result: its integer
running maximum makes the answer depend on the blocks.  The configuration
states the blocks as a semantic the program has to keep.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import intops as I


def _vocab_padded(arch: dict) -> int:
    return (arch["vocab"] + 255) // 256 * 256


def ref_arch(cfg: dict) -> dict:
    """The reference's view of a configuration file: its sizes, its grids
    and, for an encoder, the key block of its attention."""
    out = dict(cfg["arch"], quant=cfg["quant"])
    if cfg["arch"]["family"] == "encoder":
        out["attention_block"] = cfg["serve"]["attention_block"]
    return out


def _codes(key, shape, sigma: float):
    return jnp.clip(jnp.rint(jax.random.normal(key, shape) * sigma), -127, 127).astype(jnp.int8)


def code_shapes(arch: dict) -> dict:
    """Weight shapes of a configuration, stacked over layers."""
    d, h, hkv, hd = arch["d_model"], arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    n, f = arch["n_layers"], arch["d_ff"]
    qkv = (h + 2 * hkv) * hd
    if arch["family"] == "encoder":
        out = {"wqkv": (n, d, qkv), "wo": (n, h * hd, d), "up": (n, d, f),
               "down": (n, f, d), "pos": (arch["max_seq"], d)}
        if arch["vocab"]:
            out["embed"] = (arch["vocab"], d)
        return out
    return {"wqkv": (n, d, qkv), "wo": (n, h * hd, d), "gate": (n, d, f),
            "up": (n, d, f), "down": (n, f, d), "embed": (_vocab_padded(arch), d)}


def make_codes(arch: dict, weights: dict, seed: int) -> dict:
    """Every weight as int8 codes drawn from ``seed`` (jitted, on device).

    Linear weights have the spread of a unit-variance init scaled by
    ``1/sqrt(fan_in)`` on the ``s_w`` grid; the embedding and position
    tables use the code spread ``weights`` states."""
    shapes = code_shapes(arch)
    q = arch["quant"]

    def draw(key):
        keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
        out = {}
        for name, shape in shapes.items():
            if name in ("embed", "pos"):
                sigma = weights[f"{name}_code_std"]
            else:
                sigma = 1.0 / math.sqrt(shape[1]) / q["s_w"]
            out[name] = _codes(keys[name], shape, sigma)
        return out

    # the seed may exceed 32 bits: fold its high word into the key
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(draw)(key)


def int4_codes(codes: dict) -> dict:
    """The control's weights: each tensor rounded to 4 bits (per-tensor
    absmax scale), then written back on the int8 grid."""
    def four(c):
        s = jnp.maximum(jnp.max(jnp.abs(c.astype(jnp.float32))), 1.0) / 7.0
        return jnp.clip(jnp.rint(jnp.clip(jnp.rint(c / s), -7, 7) * s), -127, 127).astype(jnp.int8)

    return jax.jit(lambda cs: {k: four(v) for k, v in cs.items()})(codes)


def float_params(arch: dict, codes: dict) -> dict:
    """The codes as the program's float parameter tree (its ``init_params``
    layout), each value exactly ``code * grid`` so PTQ returns the code."""
    q = arch["quant"]
    s_w, s_res = q["s_w"], q["s_res"]

    def build(cs):
        f = lambda c, s: c.astype(jnp.float32) * jnp.float32(s)  # noqa: E731
        if arch["family"] == "encoder":
            n, d = arch["n_layers"], arch["d_model"]
            ln = {"g": jnp.ones((n, d), jnp.float32), "b": jnp.zeros((n, d), jnp.float32)}
            lin = lambda c: {"w": f(c, s_w), "b": jnp.zeros(c.shape[::2], jnp.float32)}  # noqa: E731
            out = {
                "layers": {"norm1": ln, "norm2": dict(ln),
                           "attn": {"wqkv": lin(cs["wqkv"]), "wo": lin(cs["wo"])},
                           "mlp": {"up": lin(cs["up"]), "down": lin(cs["down"])}},
                "pos": f(cs["pos"], s_res),
                "final_norm": {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)},
            }
            if "embed" in cs:
                out["embed"] = {"table": f(cs["embed"], s_res)}
            return out
        return {
            "embed": {"table": f(cs["embed"], s_res)},
            "layers": {"norm1": {}, "norm2": {},
                       "attn": {"wqkv": {"w": f(cs["wqkv"], s_w)}, "wo": {"w": f(cs["wo"], s_w)}},
                       "mlp": {k: {"w": f(cs[k], s_w)} for k in ("gate", "up", "down")}},
            "final_norm": {},
        }

    return jax.jit(build)(codes)


def _heads(x, n: int, hd: int):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)


def _merge(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def key_blocks(arch: dict, serve: dict) -> tuple[int, int, int]:
    """(key rows of one request, prompt-row block, generated-row block)."""
    bs = serve["kv_block_size"]
    rows = -(-serve["max_len"] // bs) * bs
    out = []
    for blk in (serve["attention_block"]["prompt"], serve["attention_block"]["generated"]):
        b = min(blk, rows)
        out.append(rows if rows % b else b)
    return rows, out[0], out[1]


def _decoder_logits(arch, serve, cs, tokens, n_prompt):
    """Logits of every row of one request [1, R] (float32 [R, vocab])."""
    q = arch["quant"]
    a, r, w = q["s_act"], q["s_res"], q["s_w"]
    h, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    _, blk_p, blk_g = key_blocks(arch, serve)
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def layer(x, lw):
        hn = I.layernorm(x, a)
        qkv = I.linear(hn, lw["wqkv"], a, w, a)
        qh = I.rope(_heads(qkv[..., : h * hd], h, hd), pos, arch["rope_theta"])
        kh = I.rope(_heads(qkv[..., h * hd:(h + hkv) * hd], hkv, hd), pos, arch["rope_theta"])
        vh = _heads(qkv[..., (h + hkv) * hd:], hkv, hd)
        att_p = I.attention(qh, kh, vh, pos, blk_p, a, hd)
        att_g = I.attention(qh, kh, vh, pos, blk_g, a, hd)
        att = jnp.where((pos < n_prompt)[None, None, :, None], att_p, att_g)
        x = I.iadd(x, I.linear(_merge(att), lw["wo"], a, w, a), r, a, r)
        hn = I.layernorm(x, a)
        sg = I.isilu(I.linear(hn, lw["gate"], a, w, a), a, a)
        prod = jnp.asarray(sg, jnp.int32) * jnp.asarray(I.linear(hn, lw["up"], a, w, a), jnp.int32)
        x = I.iadd(x, I.linear(I.requantize(prod, *I.qparams(a, a, a)), lw["down"], a, w, a),
                   r, a, r)
        return x, None

    layers = {k: cs[k] for k in ("wqkv", "wo", "gate", "up", "down")}
    x, _ = jax.lax.scan(layer, cs["embed"][tokens], layers)
    hn = I.layernorm(x, a)[0]
    acc = jnp.matmul(hn, cs["embed"].T, preferred_element_type=jnp.int32)
    return acc[:, : arch["vocab"]].astype(jnp.float32) * (a * w)


def _thaw(key):
    return {k: _thaw(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v
            for k, v in key}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gap(arch_key, serve_key, cs, tokens, n_prompt, targets):
    """Per row: the reference's best logit minus its logit of ``targets``."""
    ref = _decoder_logits(_thaw(arch_key), _thaw(serve_key), cs, tokens, n_prompt)
    at = jnp.take_along_axis(ref, targets[0][:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - at


@functools.partial(jax.jit, static_argnums=(0, 1))
def _argmax(arch_key, serve_key, cs, tokens, n_prompt):
    ref = _decoder_logits(_thaw(arch_key), _thaw(serve_key), cs, tokens, n_prompt)
    return jnp.argmax(ref, axis=-1).astype(jnp.int32)[None]


def _freeze(d: dict):
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v) for k, v in d.items()))


def _request_arrays(arch, serve, prompt, served):
    import numpy as np

    most, blk_p, _ = key_blocks(arch, serve)
    seq = list(prompt) + list(served)
    if len(seq) > most:
        raise ValueError(f"request of {len(seq)} rows exceeds {most}")
    # key rows past the request's own are masked for every query it has, and
    # only ever raise a block's running maximum to the floor of the int8
    # logits: whole prompt-row blocks of them change nothing, so the request
    # is run over its own rows rounded up to a prompt-row block
    rows = min(most, -(-len(seq) // blk_p) * blk_p)
    tokens = np.zeros((1, rows), np.int32)
    tokens[0, : len(seq)] = seq
    targets = np.zeros((1, rows), np.int32)
    targets[0, : len(seq) - 1] = seq[1:]
    # rows whose next token was served: the last prompt row onwards
    return tokens, targets, np.int32(len(prompt)), slice(len(prompt) - 1, len(seq) - 1)


def decoder_gap(arch: dict, serve: dict, codes: dict, prompt, served) -> float:
    """Widest logit gap by which a served token lies below the reference's
    best logit at its position (0 where every served token is greedy)."""
    import numpy as np

    tokens, targets, n, rows = _request_arrays(arch, serve, prompt, served)
    gap = _gap(_freeze(arch), _freeze(serve), codes, tokens, n, targets)
    return float(np.max(np.asarray(gap)[rows]))


def control_gap(arch: dict, serve: dict, codes: dict, control_codes: dict,
                prompt, served) -> float:
    """The control's reading on the same prompt and served tokens: at each
    position the token that ``control_codes`` put first, read by its gap
    under the reference."""
    import numpy as np

    tokens, _, n, rows = _request_arrays(arch, serve, prompt, served)
    picks = _argmax(_freeze(arch), _freeze(serve), control_codes, tokens, n)
    gap = _gap(_freeze(arch), _freeze(serve), codes, tokens, n, picks)
    return float(np.max(np.asarray(gap)[rows]))


@functools.partial(jax.jit, static_argnums=0)
def _encoder(arch_key, cs, inputs):
    arch = _thaw(arch_key)
    q = arch["quant"]
    a, r, w = q["s_act"], q["s_res"], q["s_w"]
    h, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    s, blk = inputs.shape[1], arch["attention_block"]
    g, b = jnp.full((arch["d_model"],), 64, jnp.int8), jnp.zeros((arch["d_model"],), jnp.int32)
    every = jnp.full((s,), s - 1, jnp.int32)  # bidirectional: every row sees all keys

    def layer(x, lw):
        hn = I.layernorm(x, a, g, b)
        qkv = I.linear(hn, lw["wqkv"], a, w, a)
        att = I.attention(_heads(qkv[..., : h * hd], h, hd),
                          _heads(qkv[..., h * hd:(h + hkv) * hd], hkv, hd),
                          _heads(qkv[..., (h + hkv) * hd:], hkv, hd), every, blk, a, hd)
        x = I.iadd(x, I.linear(_merge(att), lw["wo"], a, w, a), r, a, r)
        hn = I.layernorm(x, a, g, b)
        pre = I.linear(hn, lw["up"], a, w, a, gelu_pre=a)
        return I.iadd(x, I.linear(pre, lw["down"], a, w, a), r, a, r), None

    x0 = cs["embed"][inputs] if arch["vocab"] else inputs  # token ids, or int8 feature rows
    x = I.iadd(x0, cs["pos"][None, :s], r, r, r)
    x, _ = jax.lax.scan(layer, x, {k: cs[k] for k in ("wqkv", "wo", "up", "down")})
    return I.layernorm(x, a, g, b)


def encoder_features(arch: dict, codes: dict, inputs, rows: int = 16):
    """int8 codes of the final hidden states [B, S, D], ``rows`` at a time."""
    import numpy as np

    key = _freeze(arch)
    return np.concatenate([np.asarray(_encoder(key, codes, inputs[i:i + rows]))
                           for i in range(0, inputs.shape[0], rows)])
