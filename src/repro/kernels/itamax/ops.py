"""jit'd wrapper for the standalone ITAMax kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.interpret import interpret_mode
from repro.kernels.itamax.kernel import itamax_pallas


def itamax(
    logits: jnp.ndarray,  # int8 [..., n]
    *,
    block_rows: int = 256,
) -> jnp.ndarray:
    """Rowwise integer softmax over the last axis. int8 -> int8 (A, scale 2^-7)."""
    *lead, n = logits.shape
    r = int(np.prod(lead)) if lead else 1
    block_rows = min(block_rows, r)
    out = itamax_pallas(
        logits.reshape(r, n), block_rows=block_rows, interpret=interpret_mode()
    )
    return out.reshape(*lead, n)
