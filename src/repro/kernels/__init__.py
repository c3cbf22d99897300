"""Pallas TPU kernels for the compute hot-spots the paper accelerates.

Each kernel lives in ``<name>/`` with ``kernel.py`` (pl.pallas_call +
BlockSpec), ``ops.py`` (jit'd public wrapper) and ``ref.py`` (pure-jnp
oracle).  All kernels are integer-exact: tests assert bit equality against
the oracle (interpret=True on CPU, compiled on TPU).

- ``int8_gemm``      : ITA GEMM mode (int8 matmul + requant + activation)
- ``ita_attention``  : fused int8 MHA with streaming ITAMax (flash form)
- ``itamax``         : standalone rowwise integer softmax
- ``igelu``          : standalone elementwise i-GeLU
- ``silumul``        : SwiGLU's gated product with the integer SiLU
- ``expert_gemm``    : an expert layer's dropless grouped int8 GEMM
- ``paged_attention``: one decode query per lane over the paged int8 pool
"""

from repro.kernels.expert_gemm import expert_gemm, expert_gemm_ref  # noqa: F401
from repro.kernels.igelu import igelu, igelu_ref  # noqa: F401
from repro.kernels.int8_gemm import int8_gemm, int8_gemm_ref  # noqa: F401
from repro.kernels.ita_attention import ita_attention, ita_attention_ref  # noqa: F401
from repro.kernels.itamax import itamax, itamax_ref  # noqa: F401
from repro.kernels.paged_attention import paged_attention, paged_attention_ref  # noqa: F401
from repro.kernels.silumul import silumul, silumul_ref  # noqa: F401
