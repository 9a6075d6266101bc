"""The key the benchmark's random weights are made from.

A configuration's block makes the weights from it (``make_weights``, on
the device in one jitted call, in the dtype they are served in) and hands
them to the program in its parameter tree (``to_program_tree``), so that
the reference computes with weights the program did not make.
"""

from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 2**62 (seeds may exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed >> 31)
