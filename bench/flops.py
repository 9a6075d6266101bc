"""The served pool's geometry, and the decode step's least time from a
configuration's block's counts (``bench/blocks``: operations and bytes of
the algorithm, from the shapes).
"""

from __future__ import annotations

PAGE_SIZE = 64          # tokens per KV page of the served pool
LANES = 128             # a TPU vector register's minor dimension


def decode_least_seconds(block, m, steps: int, ctxs, peak_flops: float,
                         hbm_bytes_per_s: float) -> float:
    """Least time of ``steps`` decode steps that together decode one token
    for each context length in ``ctxs``.  It is the larger of all their
    operations over the peak and all their bytes over the bandwidth: the
    exact sum of each step's own bound while every step is bound by the
    same resource, which ``memory_bound_always`` checks."""
    flops = sum(block.decode_token_flops(m, c) for c in ctxs)
    nbytes = steps * block.weight_bytes_per_step(m) \
        + sum(block.decode_token_bytes(m, c) for c in ctxs)
    return max(flops / peak_flops, nbytes / hbm_bytes_per_s)


def memory_bound_always(block, m, max_batch: int, max_len: int,
                        peak_flops: float, hbm_bytes_per_s: float) -> bool:
    """Whether a decode step of ``max_batch`` rows at the longest context
    still takes longer to read its weights than to compute."""
    most = max_batch * block.decode_token_flops(m, max_len)
    return most / peak_flops < block.weight_bytes_per_step(m) \
        / hbm_bytes_per_s
