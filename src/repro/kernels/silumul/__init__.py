from repro.kernels.silumul.ops import silumul  # noqa: F401
from repro.kernels.silumul.ref import silumul_ref  # noqa: F401
