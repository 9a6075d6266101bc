"""Pallas TPU kernels for the Guardian hot paths.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper, ``interpret`` required), ref.py (pure-jnp oracle).
"""

from repro.kernels.ops import (
    flash_attention,
    gather_rows,
    moe_histogram,
    paged_attention,
    scatter_pages,
)

__all__ = ["flash_attention", "gather_rows", "moe_histogram",
           "paged_attention", "scatter_pages"]
