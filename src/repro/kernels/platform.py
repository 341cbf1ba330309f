"""Where the Pallas kernels run, decided at call time.

On a TPU the kernels compile through Mosaic.  On the CPU (tests, and
rehearsals with ``JAX_PLATFORMS=cpu``) they run in Pallas's interpreter,
which executes the same kernel body with XLA:CPU — correct, but no
measure of speed.  Any other platform has no kernels: raise rather than
fall back in silence.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU, False on a TPU; raises on any other platform."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run on 'tpu' (or interpreted on 'cpu'); "
        f"this process's default backend is {platform!r}")
