"""Jit'd public wrappers for the Pallas kernels.

Every wrapper takes ``interpret`` as a required keyword and passes it
through: ``interpret=False`` compiles the kernel for the TPU,
``interpret=True`` executes the kernel body in Python on any backend
(bit-compatible semantics, validated against ``ref.py`` in
``tests/test_kernels.py``, which asks for it because the tests run on the
CPU).  Nothing here picks a mode from the backend, so a caller on the chip
never runs the interpreter without having asked for it.
"""

from __future__ import annotations

from repro.kernels.fenced_gather import fenced_gather as _gather
from repro.kernels.fenced_paged_attention import (
    fenced_paged_attention as _paged,
)
from repro.kernels.fenced_scatter import fenced_scatter as _scatter
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_dispatch import moe_histogram as _hist


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    fence_base, fence_mask, page_map=None, *,
                    interpret: bool):
    return _paged(q, k_pages, v_pages, page_table, seq_lens,
                  fence_base, fence_mask, page_map, interpret=interpret)


def gather_rows(table, idx, fence_base, fence_mask, *, interpret: bool):
    return _gather(table, idx, fence_base, fence_mask, interpret=interpret)


def scatter_pages(pool, pages, page_ids, fence_base, fence_mask, *,
                  interpret: bool):
    return _scatter(pool, pages, page_ids, fence_base, fence_mask,
                    interpret=interpret)


def flash_attention(q, k, v, *, causal=True, q_blk=128, kv_blk=128,
                    interpret: bool):
    return _flash(q, k, v, causal=causal, q_blk=q_blk, kv_blk=kv_blk,
                  interpret=interpret)


def moe_histogram(expert_ids, num_experts, fence_base, fence_mask, *,
                  interpret: bool):
    return _hist(expert_ids, num_experts, fence_base, fence_mask,
                 interpret=interpret)
