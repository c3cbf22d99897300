"""Closed-loop batches: ``distinct_batches`` batches of ``batch`` sequences
of ``seq_len`` rows, sent back to back.

A configuration with a vocabulary takes token ids; one without takes int8
feature rows of width ``d_model`` (frame or patch embeddings after the
model's stem), drawn as codes of spread ``feature_code_std``.  The seed
draws them all.
"""

from __future__ import annotations

import numpy as np


def make(mix: dict, seconds: float, seed: int, arch: dict) -> np.ndarray:
    """int32 [n, batch, seq_len] token ids, or int8 [n, batch, seq_len,
    d_model] feature codes."""
    rng = np.random.default_rng(seed)
    shape = (int(mix["distinct_batches"]), int(mix["batch"]), int(mix["seq_len"]))
    if arch["vocab"]:
        return rng.integers(0, arch["vocab"], size=shape, dtype=np.int64).astype(np.int32)
    x = rng.standard_normal(shape + (int(arch["d_model"]),), dtype=np.float32)
    return np.clip(np.rint(x * float(mix["feature_code_std"])), -127, 127).astype(np.int8)
