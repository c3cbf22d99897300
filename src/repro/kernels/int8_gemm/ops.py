"""jit'd public wrapper for the int8 GEMM kernel (scale plumbing + shaping)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.igelu import make_igelu_params
from repro.core.quant_linear import ACT_GELU, ACT_IDENTITY
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.interpret import interpret_mode
from repro.quant.qparams import make_qparams, np_quantize_multiplier


def int8_gemm(
    x_q: jnp.ndarray,  # int8 [..., K]
    w_q: jnp.ndarray,  # int8 [K, N]
    bias_q: jnp.ndarray | None,  # int32 [N] (scale s_in * s_w)
    *,
    s_in: float,
    s_w,  # float or [N] array (per-channel)
    s_out: float,
    act: int = ACT_IDENTITY,
    s_preact: float | None = None,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
) -> jnp.ndarray:
    """Quantized linear: int8 in/out, ITA GEMM-mode semantics.

    Bit-exact vs ``repro.core.quant_linear.qlinear_i8`` with the same
    scales (the kernel accumulates over K in one int32 scratch, which is
    associative in integer arithmetic, so blocking cannot change results).
    """
    *lead, kdim = x_q.shape
    n = w_q.shape[1]
    m = int(np.prod(lead)) if lead else 1
    x2 = x_q.reshape(m, kdim)

    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)

    s_w_arr = np.asarray(s_w, np.float64).reshape(-1)
    if s_w_arr.size == 1:
        s_w_arr = np.full((n,), s_w_arr[0])
    if act == ACT_GELU:
        assert s_preact is not None
        real = s_in * s_w_arr / s_preact
    else:
        real = s_in * s_w_arr / s_out
    mult_np, shift_np = np_quantize_multiplier(real)
    mult = jnp.asarray(mult_np, jnp.int32)
    shift = jnp.asarray(shift_np, jnp.int32)
    if bias_q is None:
        bias_q = jnp.zeros((n,), jnp.int32)

    gelu = None
    gelu_mult, gelu_shift = 0, 31
    if act == ACT_GELU:
        gelu = make_igelu_params(s_preact)
        qp = make_qparams(gelu.out_scale, 1.0, s_out)
        gelu_mult, gelu_shift = qp.mult, qp.shift

    out = int8_gemm_pallas(
        x2,
        w_q,
        bias_q,
        mult,
        shift,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        act=act,
        gelu=gelu,
        gelu_mult=gelu_mult,
        gelu_shift=gelu_shift,
        interpret=interpret_mode(),
    )
    return out.reshape(*lead, n)
