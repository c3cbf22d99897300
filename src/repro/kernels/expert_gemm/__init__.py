from repro.kernels.expert_gemm.ops import expert_gemm  # noqa: F401
from repro.kernels.expert_gemm.ref import expert_gemm_ref  # noqa: F401
