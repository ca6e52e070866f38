"""jit wrapper for the SSD intra-chunk kernel (layout + backend select)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import ssd_intra_bchlp


@jax.jit
def ssd_intra(xc, dac, bc, cc):
    """xc: (B,nc,L,H,P); dac: (B,H,nc,L); bc/cc: (B,nc,L,N) -> (B,nc,L,H,P)."""
    y = ssd_intra_bchlp(
        jnp.swapaxes(xc, 2, 3), dac[:, :, :, None, :], bc, cc,
        interpret=interpret_mode(),
    )
    return jnp.swapaxes(y, 2, 3)
