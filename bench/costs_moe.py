"""Operations and bytes of a decoder whose layers are expert layers, from
shapes alone, counting activated work only: per token and layer the
attention maps, the router, the ``top_k`` routed experts, the shared
expert and its gate; attention scores at each row's depth; the head once
per sampled token.  Conventions as ``bench/costs.py`` (a multiply-accumulate
counts 2 operations; bytes are int8 operands read once)."""

from __future__ import annotations

from bench.costs import least_time  # noqa: F401 (the roofline's least time)


def expert_macs(arch: dict) -> int:
    """Multiply-accumulates of the routed experts for one token and layer."""
    return arch["top_k"] * 3 * arch["d_model"] * arch["d_ff_expert"]


def layer_macs(arch: dict) -> int:
    """Linear multiply-accumulates of one layer for one token."""
    d, h, hkv, hd = arch["d_model"], arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    attn = d * (h + 2 * hkv) * hd + h * hd * d
    fs = arch.get("n_shared_experts", 0) * arch["d_ff_expert"]
    shared = 3 * d * fs + d if fs else 0  # the fused shared experts and their gate
    return attn + d * arch["n_experts"] + expert_macs(arch) + shared


def token_ops(arch: dict) -> float:
    """Operations of the layers' linear maps for one token."""
    return 2.0 * arch["n_layers"] * layer_macs(arch)


def head_ops(arch: dict) -> float:
    """Operations of the head for one sampled token."""
    return 2.0 * arch["d_model"] * arch["vocab"]


def _score_ops(arch: dict, keys: int) -> float:
    return 4.0 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * keys


def chunk_ops(arch: dict, start: int, chunk: int, first_new: int | None = None) -> float:
    """Ops of one prefill chunk of ``chunk`` tokens at rows ``start`` on,
    each attending every row up to its own; with ``first_new``, only the
    tokens from that row on count.  The head is counted per sampled token
    (:func:`head_ops`), not here."""
    lo = start if first_new is None else max(start, first_new)
    n = start + chunk - lo
    if n <= 0:
        return 0.0
    return n * token_ops(arch) + _score_ops(arch, sum(r + 1 for r in range(lo, start + chunk)))


def decode_ops(arch: dict, lanes: int, key_rows: int) -> float:
    """Ops of one decode step: ``lanes`` tokens that attend ``key_rows``
    rows in all (the head: :func:`head_ops`)."""
    return lanes * token_ops(arch) + _score_ops(arch, key_rows)


def expert_gemm_cost(arch: dict, rows: int, groups: int) -> tuple[float, float]:
    """(ops, bytes) of the grouped expert GEMMs (gate, up, down) over
    ``rows`` routed (row, expert) pairs and ``groups`` expert groups: each
    pair's three products; each group's three weight matrices read once,
    and each pair's row in and out."""
    d, f = arch["d_model"], arch["d_ff_expert"]
    return 2.0 * 3 * d * f * rows, 3.0 * d * f * groups + 2.0 * d * rows
