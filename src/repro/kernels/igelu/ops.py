"""jit'd wrapper for the standalone i-GeLU kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.igelu import make_igelu_params
from repro.kernels.igelu.kernel import igelu_pallas
from repro.kernels.interpret import interpret_mode
from repro.quant.qparams import make_qparams


def igelu(
    x_q: jnp.ndarray,  # int8 [..., n]
    *,
    in_scale: float,
    out_scale: float,
    block_m: int = 256,
    block_n: int = 512,
) -> jnp.ndarray:
    *lead, n = x_q.shape
    m = int(np.prod(lead)) if lead else 1
    gelu = make_igelu_params(in_scale)
    qp = make_qparams(gelu.out_scale, 1.0, out_scale)
    out = igelu_pallas(
        x_q.reshape(m, n),
        gelu=gelu,
        mult=qp.mult,
        shift=qp.shift,
        block_m=min(block_m, m),
        block_n=min(block_n, n),
        interpret=interpret_mode(),
    )
    return out.reshape(*lead, n)
