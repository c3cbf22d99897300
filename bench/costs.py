"""Operations and bytes the work needs, from shapes alone.

An operation is a multiply or an add (a multiply-accumulate counts 2).
Bytes are the least that must cross HBM: each int8 operand read once, each
result written once.  The roofline's least time of a piece of work is the
larger of its operations over the peak rate and its bytes over the peak
bandwidth (``least_time``).
"""

from __future__ import annotations


def least_time(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def attention_ops(b: int, h: int, sq: int, sk: int, d: int) -> float:
    """QK^T and A.V over every (query, key) pair: 4.B.H.Sq.Sk.D."""
    return 4.0 * b * h * sq * sk * d


def attention_bytes(b: int, h: int, sq: int, sk: int, d: int) -> float:
    """int8 Q in, K and V in, the output out."""
    return float(b * h * (2 * sq * d + 2 * sk * d))


# -- a dense decoder (arch: the configuration's "arch" dict) ------------------

def decoder_linear_macs(arch: dict) -> int:
    """Multiply-accumulates of the layers' linear maps for one token."""
    d, h, hkv, hd, f = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                        arch["head_dim"], arch["d_ff"])
    mlp = (3 if arch["mlp"] == "swiglu" else 2) * d * f
    return arch["n_layers"] * (d * (h + 2 * hkv) * hd + h * hd * d + mlp)


def decoder_weight_bytes(arch: dict) -> float:
    """int8 weights read once: the layers and the (tied) LM head."""
    return float(decoder_linear_macs(arch) + arch["vocab"] * arch["d_model"])


def kv_row_bytes(arch: dict) -> int:
    """int8 K and V of one token over every layer."""
    return 2 * arch["n_layers"] * arch["n_kv_heads"] * arch["head_dim"]


def decode_step(arch: dict, lanes: int, key_rows: int) -> tuple[float, float]:
    """(ops, bytes) of one decode step: ``lanes`` active requests that
    attend ``key_rows`` rows in all, each writing one K/V row."""
    ops = lanes * (2.0 * decoder_linear_macs(arch) + 2.0 * arch["d_model"] * arch["vocab"])
    ops += 4.0 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * key_rows
    nbytes = decoder_weight_bytes(arch) + kv_row_bytes(arch) * (key_rows + lanes)
    return ops, nbytes


def chunk_ops(arch: dict, start: int, chunk: int, first_new: int | None = None) -> float:
    """Ops of one prefill chunk of ``chunk`` tokens at rows ``start`` on,
    each attending every row up to its own, and the LM head on the last.
    With ``first_new``, only the tokens from that row on count."""
    lo = start if first_new is None else max(start, first_new)
    n = start + chunk - lo
    if n <= 0:
        return 0.0
    keys = sum(r + 1 for r in range(lo, start + chunk))
    ops = n * 2.0 * decoder_linear_macs(arch) + 2.0 * arch["d_model"] * arch["vocab"]
    return ops + 4.0 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * keys


# -- an encoder ---------------------------------------------------------------

def encoder_seq_ops(arch: dict, seq: int) -> float:
    """One sequence through the executable encoder: per layer the QKV, output
    and two MLP maps, and bidirectional attention over ``seq`` keys."""
    d, h, hkv, hd, f = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                        arch["head_dim"], arch["d_ff"])
    linear = d * (h + 2 * hkv) * hd + h * hd * d + 2 * d * f
    return arch["n_layers"] * (2.0 * seq * linear + attention_ops(1, h, seq, seq, hd))
