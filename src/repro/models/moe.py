"""Mixture-of-Experts layers: the integer expert layer that is served, and
the float GShard layer of the training path.

**The integer expert layer** (w8a8).  The plan's ``moe`` nodes
(``repro.deploy.lowering``) and :func:`moe_ffn_w8a8` run the same
functions below, so every scale and rounding is stated once:

* router: ``h_q . W_r`` as exact int32 logits over all ``E`` experts;
* top-k (:func:`route_int`): chosen on the int32 logits, ties to the lowest
  expert index;
* weights: ITAMax (``core/itamax.py``) over the logits requantised to int8
  on the router grid ``2^ROUTER_SHIFT`` ITAMax steps wide, over all ``E``
  logits (``norm_topk_prob`` off: the weights are not renormalised) or
  over the ``k`` chosen (on); int8 in Q0.7;
* dispatch (:func:`dispatch`): the (row, k) pairs sorted by expert, each
  expert's group padded to the row tile; rows of parked lanes are routed
  to no expert and cost no expert work;
* experts: grouped int8 GEMMs ``gate`` and ``up`` (``kernels/expert_gemm``),
  SwiGLU's gated product (``silumul``, which skips the tiles past the last
  live one as the GEMMs do), grouped ``down``, all on ``s_act``;
* combine (:func:`combine_int`): ``requant(sum_k w_k * y_k)`` onto ``s_act``;
* shared expert: a SwiGLU block of ``cfg.shared_width``, its output scaled
  by a 256-entry sigmoid table over the int8 code of ``h_q . w_g``
  (:func:`shared_gate_int`), then added to the routed sum.

**The float layer** (:func:`moe_ffn`) is the training path's alone: GShard
capacity dispatch (tokens past ``capacity_factor`` are dropped), experts
padded to a multiple of the model-parallel axis so expert parallelism
divides evenly (padded experts are masked in the router and receive no
tokens).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import itamax as im
from repro.models import layers as L
from repro.quant.qparams import make_qparams, requantize

EP_PAD_TO = 16  # model-axis size of the production mesh
DISPATCH_GROUP = 1024  # tokens per dispatch group

#: router logits are requantised to int8 steps of 2^ROUTER_SHIFT ITAMax
#: steps (ln 2 / 16 nats): int8 spans +-5.5 nats around 0
ROUTER_SHIFT = 1
S_ROUTER = (1 << ROUTER_SHIFT) * im.ITAMAX_LOGIT_SCALE
#: grid of the shared gate's int8 logit code (+-8 in logit units)
S_GATE = 1.0 / 16.0
#: Q0.7 router weights, Q0.8 shared-gate sigmoid
S_WEIGHT = 2.0 ** -im.A_BITS
S_SIGMOID = 2.0 ** -8
#: rows of one tile of the grouped GEMM: each expert's group is padded to it
ROW_TILE = 32


def n_experts_padded(cfg: ArchConfig) -> int:
    return int(math.ceil(cfg.n_experts / EP_PAD_TO) * EP_PAD_TO)


# ---------------------------------------------------------------------------
# The integer expert layer
# ---------------------------------------------------------------------------

class MoeParams(NamedTuple):
    """Static ints of the integer expert layer."""

    router_mult: int
    router_shift: int
    combine_mult: int
    combine_shift: int
    gate_table: tuple  # 256 Q0.8 sigmoid values, indexed by code + 128
    gate_mult: int
    gate_shift: int


def sigmoid_table(s_gate: float) -> np.ndarray:
    """``round(256 * sigmoid(c * s_gate))`` for the int8 codes c = -128..127."""
    c = np.arange(-128, 128, dtype=np.float64) * s_gate
    return np.rint(256.0 / (1.0 + np.exp(-c))).astype(np.int32)


def moe_params(s_act: float, s_w: float) -> MoeParams:
    r = make_qparams(s_act, s_w, S_ROUTER)
    c = make_qparams(s_act, S_WEIGHT, s_act)
    g = make_qparams(s_act, S_SIGMOID, s_act)
    table = tuple(int(v) for v in sigmoid_table(S_GATE))
    return MoeParams(r.mult, r.shift, c.mult, c.shift, table, g.mult, g.shift)


def router_logits(h_q: jnp.ndarray, w_q: jnp.ndarray) -> jnp.ndarray:
    """int8 ``h`` [..., D] . int8 ``W_r`` [D, E] -> exact int32 logits."""
    return jnp.matmul(h_q.astype(jnp.int8), w_q.astype(jnp.int8),
                      preferred_element_type=jnp.int32)


def topk_int(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Indices of the ``k`` largest int32 logits, largest first, ties to the
    lowest index (``argmax`` returns the first maximum)."""
    e = logits.shape[-1]
    lanes = jnp.arange(e, dtype=jnp.int32)
    x, ids = logits, []
    for _ in range(k):
        i = jnp.argmax(x, axis=-1).astype(jnp.int32)
        ids.append(i)
        x = jnp.where(lanes == i[..., None], jnp.int32(-(1 << 31)), x)
    return jnp.stack(ids, axis=-1)


def route_int(logits: jnp.ndarray, valid, *, top_k: int, norm_topk: bool,
              mult: int, shift: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int32 logits [..., E] -> (expert ids [..., k] int32, weights [..., k]
    int8 in Q0.7).  Rows where ``valid`` is False route to no expert: id
    ``E`` and weight 0."""
    e = logits.shape[-1]
    ids = topk_int(logits, top_k)
    codes = requantize(logits, mult, shift)
    mask = None
    if norm_topk:
        mask = (jnp.arange(e, dtype=jnp.int32) == ids[..., None]).any(axis=-2)
    # a code's step is 2^ROUTER_SHIFT ITAMax steps: widen it onto ITAMax's
    probs = im.itamax_rowwise(jnp.asarray(codes, jnp.int32) << ROUTER_SHIFT, mask)
    w = jnp.take_along_axis(probs, ids, axis=-1)
    if valid is not None:
        keep = jnp.asarray(valid, bool)[..., None]
        ids = jnp.where(keep, ids, jnp.int32(e))
        w = jnp.where(keep, w, jnp.int8(0))
    return ids, w


def grouped_rows(n_pairs: int, n_experts: int, row_tile: int) -> tuple[int, int]:
    """(tiles, rows) of the static grouped buffer for ``n_pairs`` pairs:
    every expert's group padded to ``row_tile`` fits in
    ``ceil(n_pairs / row_tile) + min(E, n_pairs)`` tiles."""
    tiles = -(-n_pairs // row_tile) + min(n_experts, n_pairs)
    return tiles, tiles * row_tile


def dispatch(h_q: jnp.ndarray, ids: jnp.ndarray, n_experts: int, row_tile: int):
    """Sort the (row, k) pairs by expert.

    ``h_q`` int8 [R, D], ``ids`` int32 [R, k] (``n_experts``: no expert).
    Returns ``x`` int8 [P, D] (each expert's rows, its group padded to
    ``row_tile`` with zero rows, the groups in expert order), ``dest``
    int32 [R, k] (the row of ``x`` each pair went to; ``P`` for none) and
    ``tiles`` int32 [T + 3]: the expert of each tile of ``x`` (tiles past
    the last live one repeat its expert), then the live tiles, the pairs
    routed and the experts with at least one row.
    """
    r, k = ids.shape
    n = r * k
    t, p = grouped_rows(n, n_experts, row_tile)
    flat = ids.reshape(-1)
    counts = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)[:n_experts]
    group_tiles = (counts + row_tile - 1) // row_tile
    start = (jnp.cumsum(group_tiles) - group_tiles) * row_tile
    first = jnp.cumsum(counts) - counts  # first pair of each expert, sorted
    order = jnp.argsort(flat, stable=True)
    e_sorted = flat[order]
    e_safe = jnp.minimum(e_sorted, n_experts - 1)
    rank = jnp.arange(n, dtype=jnp.int32) - first[e_safe]
    slot = jnp.where(e_sorted < n_experts, start[e_safe] + rank, p)
    dest = jnp.zeros((n,), jnp.int32).at[order].set(slot)
    src = jnp.full((p,), -1, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32) // k, mode="drop")
    x = jnp.where((src >= 0)[:, None], h_q[jnp.maximum(src, 0)], jnp.int8(0))
    live = jnp.sum(group_tiles)
    last = jnp.minimum(jnp.arange(t, dtype=jnp.int32), jnp.maximum(live - 1, 0))
    tile_e = jnp.searchsorted(jnp.cumsum(group_tiles), last, side="right")
    tiles = jnp.concatenate([
        jnp.minimum(tile_e, n_experts - 1).astype(jnp.int32),
        jnp.stack([live, jnp.sum(counts), jnp.sum(counts > 0)]).astype(jnp.int32),
    ])
    return x, dest.reshape(r, k), tiles


def combine_int(y: jnp.ndarray, dest: jnp.ndarray, w: jnp.ndarray,
                mult: int, shift: int) -> jnp.ndarray:
    """``requant(sum_k w_k * y[dest_k])``: int8 [P, D] expert outputs,
    [R, k] slots and Q0.7 weights -> int8 [R, D] on the expert grid."""
    g = y[jnp.minimum(dest, y.shape[0] - 1)]  # pairs routed nowhere weigh 0
    acc = jnp.sum(jnp.asarray(w, jnp.int32)[..., None] * jnp.asarray(g, jnp.int32), axis=-2)
    return requantize(acc, mult, shift)


def shared_gate_int(sh_q: jnp.ndarray, gate_q: jnp.ndarray, table: tuple,
                    mult: int, shift: int) -> jnp.ndarray:
    """The shared expert's output [..., D] times the Q0.8 sigmoid of its
    int8 gate code [..., 1], requantised onto the output grid."""
    sig = jnp.asarray(table, jnp.int32)[jnp.asarray(gate_q, jnp.int32) + 128]
    return requantize(jnp.asarray(sh_q, jnp.int32) * sig, mult, shift)


def live_rows(tiles, row_tile: int) -> jnp.ndarray:
    """Rows of the grouped buffer that belong to a live tile: the rest are
    padding past the last group."""
    return tiles[tiles.shape[0] - 3] * row_tile


def expert_counts(tiles) -> jnp.ndarray:
    """(pairs routed, experts with rows) of one layer's dispatch."""
    t = tiles.shape[0] - 3
    return tiles[t + 1:]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_moe_layer(cfg: ArchConfig, key, dtype) -> dict:
    e = n_experts_padded(cfg)
    d, f = cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 6)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e), dtype) * 0.02},
        "experts": {
            "gate": jax.random.normal(ks[1], (e, d, f), dtype) / math.sqrt(d),
            "up": jax.random.normal(ks[2], (e, d, f), dtype) / math.sqrt(d),
            "down": jax.random.normal(ks[3], (e, f, d), dtype) / math.sqrt(f),
        },
    }
    if cfg.shared_width:
        p["shared"] = L.init_mlp(ks[4], d, cfg.shared_width, "swiglu", dtype)
        p["shared_gate"] = L.init_linear(ks[5], d, 1, False, dtype)
    return p


def init_qmoe_layer(cfg: ArchConfig, key) -> dict:
    """Shape-only integer expert layer (dry-run / synthetic serving)."""
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 8)
    rnd = lambda k, shape: jax.random.randint(k, shape, -127, 128, jnp.int8)  # noqa: E731
    p = {
        "router": {"w_q": rnd(ks[0], (d, e))},
        "experts": {"gate_q": rnd(ks[1], (e, d, f)), "up_q": rnd(ks[2], (e, d, f)),
                    "down_q": rnd(ks[3], (e, f, d))},
    }
    if cfg.shared_width:
        fs = cfg.shared_width
        p["shared"] = {"gate": L.init_qlinear(ks[4], d, fs, False),
                       "up": L.init_qlinear(ks[5], d, fs, False),
                       "down": L.init_qlinear(ks[6], fs, d, False)}
        p["shared_gate"] = L.init_qlinear(ks[7], d, 1, False)
    return p


def quantize_moe_layer(cfg: ArchConfig, p: dict, quant_w) -> dict:
    """Float expert layer (experts padded) -> the integer layout of
    :func:`init_qmoe_layer` (the published ``E`` experts)."""
    e = cfg.n_experts
    out = {
        "router": {"w_q": quant_w(p["router"]["w"][:, :e])},
        "experts": {f"{k}_q": quant_w(p["experts"][k][:e]) for k in ("gate", "up", "down")},
    }
    if "shared" in p:
        out["shared"] = {k: {"w_q": quant_w(v["w"])} for k, v in p["shared"].items()}
        out["shared_gate"] = {"w_q": quant_w(p["shared_gate"]["w"])}
    return out


# ---------------------------------------------------------------------------
# Float path (training): GShard capacity dispatch
# ---------------------------------------------------------------------------

def _route(cfg: ArchConfig, router_w: jnp.ndarray, h_f32: jnp.ndarray):
    """h [G, g, D] -> dispatch [G, g, E, C] bool-ish, combine [G, g, E, C] f32."""
    e = n_experts_padded(cfg)
    g_tokens = h_f32.shape[1]
    cap = int(math.ceil(g_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    cap = max(cap, cfg.top_k)
    logits = jnp.einsum("gtd,de->gte", h_f32, router_w.astype(jnp.float32))
    if e != cfg.n_experts:  # mask padded experts
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, None, :], -1e9, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)  # [G, g, K]
    if cfg.norm_topk_prob:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [G, g, K, E]
    flat = onehot.reshape(onehot.shape[0], -1, e)  # [G, g*K, E] in (t, k) order
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(onehot.shape)  # [G,g,K,E]
    pos = jnp.einsum("gtke,gtke->gtk", pos_in_expert, onehot).astype(jnp.int32)  # [G,g,K]
    keep = pos < cap
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    # dispatch/combine [G, g, E, C]
    dispatch_ = jnp.einsum("gtke,gtkc->gtec", onehot, pos_oh)
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", topv, onehot, pos_oh)
    aux = _load_balance_loss(probs[..., : cfg.n_experts], onehot[..., : cfg.n_experts])
    return dispatch_, combine, aux


def _load_balance_loss(probs, onehot):
    """Switch-style auxiliary load-balancing loss."""
    density = onehot.sum(2).mean(1)  # [G, E] fraction routed
    density_proxy = probs.mean(1)  # [G, E] mean router prob
    e = probs.shape[-1]
    return (density * density_proxy).sum(-1).mean() * e


def _group(x: jnp.ndarray, g: int):
    t = x.shape[0]
    if t % g:
        g = t  # single group fallback for odd token counts
    return x.reshape(t // g, g, *x.shape[1:]), g


def moe_ffn(cfg: ArchConfig, p: dict, h: jnp.ndarray):
    """h [B, S, D] -> (out [B, S, D], aux_loss)."""
    from repro.runtime.activations import constrain

    b, s, d = h.shape
    flat = h.reshape(b * s, d)
    grouped, g = _group(flat, DISPATCH_GROUP)
    dispatch_, combine, aux = _route(cfg, p["router"]["w"], grouped.astype(jnp.float32))
    xe = jnp.einsum("gtec,gtd->gecd", dispatch_.astype(h.dtype), grouped)
    xe = constrain(xe, "experts")
    ge = jnp.einsum("gecd,edf->gecf", xe, p["experts"]["gate"])
    ue = jnp.einsum("gecd,edf->gecf", xe, p["experts"]["up"])
    ye = jnp.einsum("gecf,efd->gecd", L.silu(ge) * ue, p["experts"]["down"])
    out = jnp.einsum("gtec,gecd->gtd", combine.astype(h.dtype), ye)
    out = out.reshape(b, s, d)
    if "shared" in p:
        sh = L.mlp_forward(p["shared"], h, "swiglu")
        out = out + sh * jax.nn.sigmoid(L.linear(p["shared_gate"], h))
    return out, aux


# ---------------------------------------------------------------------------
# Integer path: the model-level composition of the layer above
# ---------------------------------------------------------------------------

def moe_ffn_w8a8(cfg: ArchConfig, lp: dict, h_q: jnp.ndarray, q: L.QuantConfig):
    """h_q int8 [B, S, D] (s_act grid) -> int8 [B, S, D] (s_act grid); the
    expert GEMMs run through their XLA runner and every row is routed."""
    from repro.kernels.expert_gemm import expert_gemm_ref

    b, s, d = h_q.shape
    mp = moe_params(q.s_act, q.s_w)
    site = L.QLinearSite(q.s_act, q.s_w, q.s_act)
    gemm_scales = (q.s_act, q.s_w, q.s_act)
    silu_scales = (q.s_act, q.s_act, q.s_act)
    logits = router_logits(h_q, lp["router"]["w_q"])
    ids, w = route_int(logits, None, top_k=cfg.top_k, norm_topk=cfg.norm_topk_prob,
                       mult=mp.router_mult, shift=mp.router_shift)
    x, dest, tiles = dispatch(h_q.reshape(b * s, d), ids.reshape(b * s, -1),
                              cfg.n_experts, ROW_TILE)
    ex = lp["experts"]
    g = expert_gemm_ref(x, tiles, ex["gate_q"], scales=gemm_scales, row_tile=ROW_TILE)
    u = expert_gemm_ref(x, tiles, ex["up_q"], scales=gemm_scales, row_tile=ROW_TILE)
    a = L.silumul_int(g, u, L.silumul_params(*silu_scales))
    y = expert_gemm_ref(a, tiles, ex["down_q"], scales=gemm_scales, row_tile=ROW_TILE)
    out_q = combine_int(y, dest, w.reshape(b * s, -1), mp.combine_mult,
                        mp.combine_shift).reshape(b, s, d)
    if "shared" in lp:
        sh = lp["shared"]
        gs = L.qlinear(sh["gate"], h_q, site)
        us = L.qlinear(sh["up"], h_q, site)
        sh_out = L.qlinear(sh["down"], L.silumul_int(gs, us, L.silumul_params(*silu_scales)),
                           site)
        code = L.qlinear(lp["shared_gate"], h_q, L.QLinearSite(q.s_act, q.s_w, S_GATE))
        sh_out = shared_gate_int(sh_out, code, mp.gate_table, mp.gate_mult, mp.gate_shift)
        out_q = L.iadd_i8(out_q, sh_out, *L.make_iadd_params(q.s_act, q.s_act, q.s_act))
    return out_q
