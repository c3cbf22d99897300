from repro.kernels.paged_attention.ops import paged_attention  # noqa: F401
from repro.kernels.paged_attention.ref import paged_attention_ref  # noqa: F401
