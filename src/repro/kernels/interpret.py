"""Where the Pallas kernels run: compiled on a TPU, interpreted on a CPU.

This is the one place that decides.  Any other platform raises instead of
falling back to interpret mode, which would hide that the kernels never
reached the device.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU (tests), False on the TPU; raises anywhere else."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for the TPU and are interpreted on the "
        f"CPU; there is no kernel path for platform {platform!r}"
    )
