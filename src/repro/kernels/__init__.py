"""Pallas TPU kernels: each package holds the kernel (``kernel.py``), its jit
wrapper (``ops.py``) and a pure-jnp oracle (``ref.py``).

The kernel functions take ``interpret`` explicitly.  The wrappers ask
``interpret_mode`` once per trace, so a kernel is interpreted only where the
devices in use are not TPUs (the CPU test suite) and never on a TPU.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels must run interpreted: True off the TPU."""
    return jax.default_backend() != "tpu"
