"""Production mesh definitions.

A *function*, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any device
query; tests/smoke runs see the real 1-device CPU).

Mesh geometry (TPU v5e pods):

  single-pod:  (16, 16)    axes (data, model)          = 256 chips
  multi-pod:   (2, 16, 16) axes (pod, data, model)     = 512 chips

The ``pod`` axis is the CNA locality domain: ICI inside a pod (fast,
"same-socket" handover), DCN across pods (slow, the remote-socket transfer
the paper's admission policy avoids).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.  The model code places
    arrays with ``with_sharding_constraint`` over logical axes
    (``repro.models.sharding``), which ``Explicit`` axes (jax's default)
    refuse."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(AxisType.Auto,) * len(shape),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Largest mesh on the visible devices (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


# -- hardware constants (TPU v5e per chip; see EXPERIMENTS.md §Roofline) -----
PEAK_FLOPS_BF16 = 197e12         # FLOP/s
HBM_BW = 819e9                   # bytes/s
ICI_BW_PER_LINK = 50e9           # bytes/s/link (one direction)
ICI_LINKS_PER_AXIS = 2           # bidirectional ring on one torus axis
ICI_BW = ICI_LINKS_PER_AXIS * ICI_BW_PER_LINK   # ring-collective BW per chip
DCN_BW = 25e9                    # bytes/s per chip across pods (assumption)
CHIPS_PER_POD = 256
