"""Where the Pallas kernels run: compiled by Mosaic on the TPU, interpreted on
the CPU (where the tests run them), and nowhere else."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """``interpret=`` for a ``pallas_call`` on the default backend.

    False on ``"tpu"`` (compiled), True on ``"cpu"`` (the Pallas interpreter).
    Any other platform raises: a kernel never falls back silently to a mode
    that hides the device it was meant for.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or interpreted "
                       f"on 'cpu'; the default backend is {platform!r}")
