"""Bounds fencing / checking primitives — Guardian §4.4 ("Bounds Checking
Tradeoffs"), adapted to TPU index spaces.

The paper instruments every PTX load/store with one of three bounds modes.
On TPU the analogous *dynamically computed address* is a data-dependent
integer index into a shared HBM arena (KV page ids, embedding rows, expert
offsets, state slots).  The fence is applied to the index *before* it is used
by a gather/scatter/DMA — the exact analogue of patching the PTX register
before ``ld.global``.

Three modes (paper costs in parentheses):

* ``BITWISE``  — ``idx' = (idx & mask) | base``  (2 instrs, ~8 cycles).
  Requires pow2-sized, size-aligned partitions (``core.partition``
  invariants I1/I2).  Wrap-around semantics: an out-of-partition index is
  remapped *into the offender's own partition*; neighbours are never touched.
* ``MODULO``   — ``idx' = base + ((idx - base) mod size)`` (paper: ~28 cycles
  with an inline reciprocal instead of the libcall).  Works for arbitrary
  partition sizes.  We provide both the plain ``lax.rem`` form and the
  paper-faithful *reciprocal* form (`fence_modulo_magic`) built from a
  precomputed magic multiplier — no hardware divide on the hot path.
* ``CHECK``    — compare + select (paper: ~80 cycles, 1.7x app slowdown).
  The only mode that *detects* OOB; returns an ``ok`` predicate alongside a
  safe index (clamped to ``base``), so the manager can report the fault and
  kill the offending tenant kernel (fault isolation with detection).

``NONE`` is the standalone fast-path (§4.2.3: "when the grdManager detects
that an application runs standalone, it issues a native kernel").
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import Partition, is_pow2


class FencePolicy(enum.Enum):
    """Which bounds mode the manager applies (§4.4)."""

    NONE = "none"          # native kernel — standalone fast-path
    BITWISE = "bitwise"    # address fencing, bitwise AND/OR (headline mode)
    MODULO = "modulo"      # address fencing, inline modulo
    CHECK = "check"        # address checking (detects OOB; debug / strict)

    @property
    def code(self) -> int:
        """Stable int32 code for per-element policy columns in row-mixed
        batches (see :func:`apply_fence_mixed`)."""
        return _POLICY_CODE[self]


_POLICY_CODE = {
    FencePolicy.NONE: 0,
    FencePolicy.BITWISE: 1,
    FencePolicy.MODULO: 2,
    FencePolicy.CHECK: 3,
}


# ---------------------------------------------------------------------------
# Magic-number (reciprocal) unsigned division, n < 2**31.
#
# The paper implements the 64-bit modulo "inline with three instructions and
# an extra parameter holding 1/partition_size" to avoid CUDA's div libcall.
# The TPU analogue: precompute (m, s) on the host such that
#     n // d == (n * m) >> s         for all 0 <= n < 2**31,
# and evaluate the 32x32->64 high-multiply with 16-bit limbs (no int64
# needed; JAX x64 stays disabled).
# ---------------------------------------------------------------------------

_MAGIC_DOMAIN_BITS = 31  # we mask indices into [0, 2**31) first (1 extra op)


def magic_constants(d: int) -> Tuple[int, int]:
    """Precompute (m, s) with ``n // d == (n * m) >> s`` for n < 2**31.

    Uses the classic round-up method: s = 31 + ceil(log2 d), m = ceil(2^s/d).
    For this domain m always fits in 32 unsigned bits (verified by the
    hypothesis sweep in tests/test_fence.py).
    """
    if d <= 0:
        raise ValueError(f"divisor must be positive, got {d}")
    if d == 1:
        return 1, 0
    log2d = (d - 1).bit_length()  # ceil(log2 d)
    s = _MAGIC_DOMAIN_BITS + log2d
    m = (1 << s) + d - 1
    m //= d
    assert m < (1 << 32), (d, m)
    return m, s


def magic_row(d: int) -> Tuple[int, int]:
    """(m, s) for the *dynamic* magic row table.

    Identical to :func:`magic_constants` except the degenerate ``d == 1``
    divisor, whose shift (0) would underflow the traced ``s - 32``
    hi-word shift.  The dynamic fence masks the remainder to zero for
    size-1 rows (`fence_modulo_magic_dyn`), so the stored pair only needs
    a shift >= 32; (0, 32) yields q = 0 and the mask does the rest.
    """
    if d == 1:
        return 0, 32
    return magic_constants(d)


def _umul_hi32_and_shift(n: jax.Array, m: int, s: int) -> jax.Array:
    """Compute ``(n * m) >> s`` for 0 <= n < 2**31, 0 < m < 2**32, s >= 32,
    without 64-bit integers, via 16-bit limb decomposition in uint32.

    uint32 arithmetic wraps mod 2^32 and shifts logically, so the carry
    chain below is exact:

        prod = ll + (lh + hl + (ll>>16)) << 16 + hh << 32

    with each accumulation step kept < 2^32 (proof in comments).  Returns
    int32 (the quotient is < 2^31 because n < 2^31 and m/2^s <= 1/d <= 1).
    """
    n = n.astype(jnp.uint32)
    n_lo = n & jnp.uint32(0xFFFF)          # < 2^16
    n_hi = n >> jnp.uint32(16)             # < 2^15  (n < 2^31)
    m_lo = np.uint32(m & 0xFFFF)
    m_hi = np.uint32((m >> 16) & 0xFFFF)

    ll = n_lo * m_lo                       # < 2^32, exact
    lh = n_lo * m_hi                       # <= (2^16-1)^2 < 2^32 - 2^17
    hl = n_hi * m_lo                       # < 2^31
    hh = n_hi * m_hi                       # < 2^31

    mid1 = lh + (ll >> jnp.uint32(16))     # < (2^16-1)^2 + 2^16 < 2^32, exact
    # mid1 + hl may exceed 2^32 -> split into 16-bit halves with carry.
    mid_lo = (mid1 & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))  # < 2^17
    mid_hi = (mid1 >> jnp.uint32(16)) + (hl >> jnp.uint32(16)) + (
        mid_lo >> jnp.uint32(16)
    )                                       # < 2^16 + 2^15 + 2 < 2^17
    hi = hh + mid_hi                        # < 2^31 + 2^17 < 2^32, exact hi word

    sh = s - 32
    assert 0 <= sh < 32, s
    q = hi >> jnp.uint32(sh) if sh else hi
    return q.astype(jnp.int32)


def _umul_hi32_and_shift_dyn(n: jax.Array, m, s) -> jax.Array:
    """Traced-magic twin of :func:`_umul_hi32_and_shift`.

    ``m``/``s`` arrive as *dynamic* operands — int32 scalars or arrays from
    a magic row table (``m`` is the uint32 multiplier's bit pattern stored
    in int32) — instead of Python constants, so one compiled binary serves
    any tenant set.  Same 16-bit-limb carry chain, same exactness proof
    (n < 2^31, m < 2^32).  ``s`` must be >= 32 (guaranteed by
    :func:`magic_row` for every divisor).
    """
    n = jnp.asarray(n).astype(jnp.uint32)
    m = jax.lax.bitcast_convert_type(jnp.asarray(m, jnp.int32), jnp.uint32)
    n_lo = n & jnp.uint32(0xFFFF)
    n_hi = n >> jnp.uint32(16)
    m_lo = m & jnp.uint32(0xFFFF)
    m_hi = m >> jnp.uint32(16)

    ll = n_lo * m_lo
    lh = n_lo * m_hi
    hl = n_hi * m_lo
    hh = n_hi * m_hi

    mid1 = lh + (ll >> jnp.uint32(16))
    mid_lo = (mid1 & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))
    mid_hi = (mid1 >> jnp.uint32(16)) + (hl >> jnp.uint32(16)) + (
        mid_lo >> jnp.uint32(16))
    hi = hh + mid_hi

    sh = (jnp.asarray(s, jnp.int32) - 32).astype(jnp.uint32)
    q = hi >> sh
    return q.astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class FenceParams:
    """The per-tenant scalar row passed to kernels (paper: "two extra kernel
    parameters" -> 2 registers; here: scalar operands -> SMEM).

    ``base``/``size`` may be Python ints (static — per-tenant specialized
    binary, which the paper rejects as unscalable) **or traced int32 scalars**
    (dynamic — one shared binary, bounds passed at launch time, the paper's
    actual design).  MODULO historically required a concrete size (the
    shift amount was structural, one binary per partition); with
    ``magic_m``/``magic_s`` populated — int32 scalars or arrays carrying a
    precomputed reciprocal row from a :class:`FenceTable` magic table —
    MODULO too becomes a dynamic-operand mode and fuses like BITWISE.
    """

    base: Any
    size: Any
    magic_m: Any = None    # uint32 multiplier bit-pattern (int32-stored)
    magic_s: Any = None    # shift amount, >= 32 (see magic_row)

    def __post_init__(self):
        if isinstance(self.size, int) and self.size <= 0:
            raise ValueError("partition size must be positive")

    @property
    def is_static(self) -> bool:
        return isinstance(self.base, int) and isinstance(self.size, int)

    @property
    def mask(self):
        """``size - 1`` — only a valid bitwise fence mask for pow2 sizes.

        Static sizes are checked here.  **Traced sizes cannot be checked at
        trace time**: ``size - 1`` is returned unconditionally, and the
        wrap guarantee of BITWISE silently breaks if the traced value is
        not a power of two.  The contract is therefore that every caller
        building traced params from host-known sizes validates them first
        with :func:`require_pow2_sizes` — the manager, the serve engine and
        :class:`FenceTable` all do (partitions from the buddy allocator are
        pow2 by construction; this guards hand-built params).
        """
        if isinstance(self.size, int):
            if not is_pow2(self.size):
                raise ValueError("mask only defined for pow2 partitions")
            return self.size - 1
        return self.size - 1  # traced: caller validated via require_pow2_sizes

    @property
    def magic(self) -> Tuple[int, int]:
        if not isinstance(self.size, int):
            raise ValueError(
                "MODULO fencing needs a concrete partition size (the shift "
                "amount is structural); use static FenceParams"
            )
        return magic_constants(self.size)

    @classmethod
    def from_partition(cls, part: Partition) -> "FenceParams":
        return cls(base=part.base, size=part.size)

    def contains(self, lo: int, hi: Optional[int] = None) -> bool:
        hi = lo + 1 if hi is None else hi
        return self.base <= lo and hi <= self.base + self.size


_FP_FIELDS = ("base", "size", "magic_m", "magic_s")


def _fence_params_flatten(fp: "FenceParams"):
    """Pytree flattening with a *per-instance* static/dynamic split.

    Array-valued fields (traced bounds, magic-row columns, or their
    ``jax.ShapeDtypeStruct`` stand-ins when a step is only lowered) are
    children so FenceParams can ride through ``jax.jit`` as an operand
    (the jitted trusted-step path passes GuardSpecs this way); host-int
    fields stay aux data so a static-bounds guard keeps its concrete
    values — the
    MODULO static path needs a concrete size for its shift amount, and
    baking static bounds into the compiled step matches the eager path
    bit-for-bit.
    """
    vals = tuple(getattr(fp, n) for n in _FP_FIELDS)
    is_dyn = tuple(isinstance(v, (jax.Array, np.ndarray,
                                  jax.ShapeDtypeStruct)) for v in vals)
    children = tuple(v for v, d in zip(vals, is_dyn) if d)
    static = tuple(None if d else v for v, d in zip(vals, is_dyn))
    return children, (is_dyn, static)


def _fence_params_unflatten(aux, children) -> "FenceParams":
    is_dyn, static = aux
    it = iter(children)
    return FenceParams(*(next(it) if d else s
                         for d, s in zip(is_dyn, static)))


jax.tree_util.register_pytree_node(
    FenceParams, _fence_params_flatten, _fence_params_unflatten)


def require_pow2_sizes(sizes) -> None:
    """Host-side guard for building *traced* fence params (see
    :attr:`FenceParams.mask`): every size must be a positive power of two.

    Accepts a scalar or any array-like of host-known ints.  Raises
    ``ValueError`` listing the offending sizes; a traced (abstract) input is
    a programming error and also raises.
    """
    arr = np.asarray(sizes)
    if arr.dtype == object or not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(
            f"require_pow2_sizes needs host-known integer sizes, got "
            f"dtype {arr.dtype}: validate before staging to device")
    flat = arr.reshape(-1).astype(np.int64)
    bad = flat[(flat <= 0) | ((flat & (flat - 1)) != 0)]
    if bad.size:
        raise ValueError(
            f"partition sizes must be positive powers of two for bitwise "
            f"fencing (invariant I1); offenders: {sorted(set(bad.tolist()))}")


@dataclasses.dataclass(frozen=True)
class FenceTable:
    """Stacked per-tenant fence rows — the batched form of
    :class:`FenceParams` (one ``(base, mask)`` int32 row per tenant).

    This is what the batched multi-tenant scheduler passes to a fused
    device step: a single ``(T, 2)`` int32 table of dynamic scalars, so one
    compiled binary serves any set of tenants (the paper's "two extra
    kernel parameters", vectorized across tenants — no per-tenant
    recompiles).  Row ``r`` fences row ``r`` of the fused batch; a
    tenant-id *column* can gather per-element params for row-mixed batches
    (the serving engine's per-row guard).

    ``magic`` is the optional per-row magic-constant table that lets
    MODULO batches fuse too: ``(T, 4)`` int32 of ``(base, size, m, s)``
    built from :func:`magic_row`.  Unlike the bitwise ``rows`` it supports
    arbitrary (non-pow2) partition sizes — a magic-only table
    (:meth:`modulo_from_bounds`) has ``rows is None``.
    """

    rows: Optional[jax.Array] = None   # (T, 2) int32: rows[r] = (base, mask)
    magic: Optional[jax.Array] = None  # (T, 4) int32: (base, size, m, s)

    def __post_init__(self):
        if self.rows is None and self.magic is None:
            raise ValueError("FenceTable needs bitwise rows, a magic "
                             "table, or both")

    @staticmethod
    def _magic_arr(bounds: Sequence[Tuple[int, int]]) -> jax.Array:
        arr = np.zeros((len(bounds), 4), np.uint32)
        for r, (base, size) in enumerate(bounds):
            m, s = magic_row(size)
            arr[r] = (base, size, m, s)
        return jnp.asarray(arr.view(np.int32))

    @classmethod
    def from_partitions(cls, parts: Sequence[Partition],
                        with_magic: bool = False) -> "FenceTable":
        if not parts:
            raise ValueError("FenceTable needs at least one partition")
        require_pow2_sizes([p.size for p in parts])
        arr = np.array([[p.base, p.mask] for p in parts], dtype=np.int32)
        magic = cls._magic_arr([(p.base, p.size) for p in parts]) \
            if with_magic else None
        return cls(rows=jnp.asarray(arr), magic=magic)

    @classmethod
    def from_bounds(cls, base, size) -> "FenceTable":
        """Build from host (base, size) arrays, validating pow2 sizes."""
        base = np.asarray(base, np.int32).reshape(-1)
        size = np.asarray(size, np.int64).reshape(-1)
        require_pow2_sizes(size)
        arr = np.stack([base, (size - 1).astype(np.int32)], axis=1)
        return cls(rows=jnp.asarray(arr.astype(np.int32)))

    @classmethod
    def modulo_from_bounds(cls, base, size) -> "FenceTable":
        """Magic-only table for arbitrary (incl. non-pow2) partition sizes.

        No bitwise rows are built — a non-pow2 ``size - 1`` is not a valid
        wrap mask — so the table fences through MODULO/CHECK only.
        """
        base = np.asarray(base, np.int64).reshape(-1)
        size = np.asarray(size, np.int64).reshape(-1)
        if (size <= 0).any():
            raise ValueError("partition sizes must be positive")
        return cls(magic=cls._magic_arr(list(zip(base.tolist(),
                                                 size.tolist()))))

    def __len__(self) -> int:
        arr = self.rows if self.rows is not None else self.magic
        return int(arr.shape[0])

    def row_params(self, row) -> FenceParams:
        """Traced FenceParams for one table row (fused-step row ``r``)."""
        if self.rows is None:
            return self.magic_row_params(row)
        return FenceParams(base=self.rows[row, 0],
                           size=self.rows[row, 1] + 1)

    def magic_row_params(self, row) -> FenceParams:
        """Traced magic-carrying FenceParams for one magic-table row."""
        if self.magic is None:
            raise ValueError("table was built without a magic row table")
        return FenceParams(base=self.magic[row, 0], size=self.magic[row, 1],
                           magic_m=self.magic[row, 2],
                           magic_s=self.magic[row, 3])

    def gather(self, tenant_col: jax.Array) -> FenceParams:
        """Per-element FenceParams for a tenant-id column.

        ``tenant_col[i]`` selects the table row fencing element ``i``; the
        returned params hold ``(N,)`` base/size arrays that broadcast
        elementwise through the fences (batched serving, §4.2.4).  When the
        table carries magic rows the params also carry per-element magic
        columns, so MODULO (and row-mixed) policies fence dynamically.
        """
        col = jnp.asarray(tenant_col, jnp.int32)
        if self.rows is not None:
            base = jnp.take(self.rows[:, 0], col, axis=0)
            mask = jnp.take(self.rows[:, 1], col, axis=0)
            base, size = base, mask + 1
        else:
            base = jnp.take(self.magic[:, 0], col, axis=0)
            size = jnp.take(self.magic[:, 1], col, axis=0)
        if self.magic is None:
            return FenceParams(base=base, size=size)
        return FenceParams(
            base=base, size=size,
            magic_m=jnp.take(self.magic[:, 2], col, axis=0),
            magic_s=jnp.take(self.magic[:, 3], col, axis=0))


# ---------------------------------------------------------------------------
# The three fences.  All take/return integer index arrays (any shape, int32).
# ---------------------------------------------------------------------------


def fence_bitwise(idx: jax.Array, base, mask) -> jax.Array:
    """``(idx & mask) | base`` — Guardian's headline mode (Listing 1).

    With base size-aligned and mask = size-1 this maps any int32 into
    [base, base+size) and is the identity inside the partition.
    """
    idx = jnp.asarray(idx, jnp.int32)
    mask = jnp.asarray(mask, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    return jnp.bitwise_or(jnp.bitwise_and(idx, mask), base)


def fence_modulo(idx: jax.Array, base, size) -> jax.Array:
    """``base + ((idx - base) mod size)`` with floor-mod semantics.

    Plain form (lets XLA lower the remainder however it likes).  Arbitrary
    partition sizes.  Matches the paper's *semantics*; the cost-faithful
    reciprocal form is `fence_modulo_magic`.
    """
    idx = jnp.asarray(idx, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    size = jnp.asarray(size, jnp.int32)
    off = idx - base
    # Bring into the non-negative domain first: floor-mod of a negative int32
    # is already non-negative in jnp, but we mirror the magic variant so the
    # two are bit-identical (see tests).
    off = jnp.bitwise_and(off, jnp.int32(0x7FFFFFFF))
    return base + jnp.remainder(off, size)


def fence_modulo_magic(idx: jax.Array, base, size, m: int, s: int) -> jax.Array:
    """Reciprocal-multiply modulo — the paper's "inline 64-bit modulo with
    three instructions and an extra parameter holding 1/partition_size".

    idx' = base + (off - (off // size) * size),  off = (idx - base) & 0x7fffffff
    where the division is a precomputed magic multiply-high + shift.
    """
    idx = jnp.asarray(idx, jnp.int32)
    if size == 1:  # degenerate partition: every access maps to base
        return jnp.full(idx.shape, base, jnp.int32)
    off = jnp.bitwise_and(idx - jnp.int32(base), jnp.int32(0x7FFFFFFF))
    q = _umul_hi32_and_shift(off, m, s)
    rem = off - q * jnp.int32(size)
    return jnp.int32(base) + rem


def fence_modulo_magic_dyn(idx: jax.Array, base, size, m, s) -> jax.Array:
    """Reciprocal modulo with *traced* magic constants — the fused-batch
    form of :func:`fence_modulo_magic`.

    ``(base, size, m, s)`` are dynamic operands (one magic row of a
    :class:`FenceTable`), so a single compiled binary fences any tenant
    set — the missing piece that historically kept MODULO launches out of
    fused device steps.  Bit-identical to the static form for every
    divisor (the division is exact either way); size-1 rows are handled by
    masking the remainder to zero (see :func:`magic_row`).
    """
    idx = jnp.asarray(idx, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    size = jnp.asarray(size, jnp.int32)
    off = jnp.bitwise_and(idx - base, jnp.int32(0x7FFFFFFF))
    q = _umul_hi32_and_shift_dyn(off, m, s)
    rem = off - q * size
    rem = jnp.where(size == 1, jnp.int32(0), rem)
    return base + rem


def apply_fence_mixed(
    codes: jax.Array,
    idx: jax.Array,
    params: FenceParams,
) -> Tuple[jax.Array, jax.Array]:
    """Per-element policy dispatch for row-mixed batches (serving plane).

    ``codes[i]`` is the :attr:`FencePolicy.code` fencing element ``i`` —
    gathered through the same tenant-id column as ``params`` — so one
    batched step can mix BITWISE, MODULO and CHECK tenants.  Requires
    ``params`` built from a magic-carrying table (``magic_m`` set) because
    the MODULO branch is compiled in unconditionally; BITWISE masks are
    only correct because partitions are pow2 by construction (buddy
    invariant I1, validated when the table was staged).

    Returns ``(fenced, ok)`` where ``ok`` is True everywhere except
    CHECK-policy elements that were out of bounds (the serving engine
    folds it into the ViolationLog).
    """
    if params.magic_m is None:
        raise ValueError(
            "apply_fence_mixed needs magic-carrying FenceParams (build the "
            "FenceTable with with_magic=True)")
    idx = jnp.asarray(idx, jnp.int32)
    codes = jnp.asarray(codes, jnp.int32)
    bitwise = fence_bitwise(idx, params.base, params.size - 1)
    modulo = fence_modulo_magic_dyn(idx, params.base, params.size,
                                    params.magic_m, params.magic_s)
    checked, ok_chk = fence_check(idx, params.base, params.size)
    fenced = jnp.select(
        [codes == FencePolicy.BITWISE.code,
         codes == FencePolicy.MODULO.code,
         codes == FencePolicy.CHECK.code],
        [bitwise, modulo, checked],
        idx)                                  # NONE: native passthrough
    ok = jnp.where(codes == FencePolicy.CHECK.code, ok_chk, True)
    return fenced, ok


def fence_check(idx: jax.Array, base, size) -> Tuple[jax.Array, jax.Array]:
    """Address checking: returns (safe_idx, ok).

    ``ok`` is False wherever idx was out of partition; safe_idx is clamped to
    ``base`` there so downstream accesses stay in-partition.  The manager
    reads ``ok`` to detect the fault (paper: "detect invalid accesses and
    return from the kernel").
    """
    idx = jnp.asarray(idx, jnp.int32)
    lo = jnp.asarray(base, jnp.int32)
    hi = lo + jnp.asarray(size, jnp.int32)
    ok = jnp.logical_and(idx >= lo, idx < hi)
    safe = jnp.where(ok, idx, lo)
    return safe, ok


def apply_fence(
    policy: FencePolicy,
    idx: jax.Array,
    params: FenceParams,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Dispatch on policy. Returns (fenced_idx, ok_or_None).

    ``ok`` is only produced by CHECK; fencing modes return None (they cannot
    detect, only contain — §4.4).
    """
    if policy is FencePolicy.NONE:
        return jnp.asarray(idx, jnp.int32), None
    if policy is FencePolicy.BITWISE:
        return fence_bitwise(idx, params.base, params.mask), None
    if policy is FencePolicy.MODULO:
        if params.magic_m is not None:
            return fence_modulo_magic_dyn(
                idx, params.base, params.size,
                params.magic_m, params.magic_s), None
        m, s = params.magic
        return fence_modulo_magic(idx, params.base, params.size, m, s), None
    if policy is FencePolicy.CHECK:
        return fence_check(idx, params.base, params.size)
    raise ValueError(f"unknown policy {policy}")


# ---------------------------------------------------------------------------
# Guarded arena ops — the XLA-level "sandboxed load/store".
#
# These are what the framework's own data paths use (paged-KV lookups,
# embedding gathers, expert dispatch) and what the jaxpr sandboxer inserts
# into tenant kernels.  Axis 0 of ``arena`` is the shared slot space.
# ---------------------------------------------------------------------------


def guarded_take(
    arena: jax.Array,
    idx: jax.Array,
    params: FenceParams,
    policy: FencePolicy = FencePolicy.BITWISE,
) -> jax.Array:
    """Fenced gather of arena rows: ``arena[fence(idx)]``."""
    fenced, _ = apply_fence(policy, idx, params)
    # The fence proves in-bounds-ness, so XLA's own OOB clamp is elided.
    return arena.at[fenced].get(mode="promise_in_bounds")


def guarded_update(
    arena: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    params: FenceParams,
    policy: FencePolicy = FencePolicy.BITWISE,
) -> jax.Array:
    """Fenced scatter of arena rows: ``arena.at[fence(idx)].set(values)``."""
    fenced, _ = apply_fence(policy, idx, params)
    return arena.at[fenced].set(values, mode="promise_in_bounds")


def guarded_add(
    arena: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    params: FenceParams,
    policy: FencePolicy = FencePolicy.BITWISE,
) -> jax.Array:
    fenced, _ = apply_fence(policy, idx, params)
    return arena.at[fenced].add(values, mode="promise_in_bounds")


def guarded_dynamic_slice(
    arena: jax.Array,
    start: jax.Array,
    length: int,
    params: FenceParams,
    policy: FencePolicy = FencePolicy.BITWISE,
) -> jax.Array:
    """Fenced contiguous read of ``length`` rows starting at ``start``.

    Both endpoints are fenced; a read that would straddle the partition end
    is pinned so it stays inside (start clamped to base+size-length).
    """
    fenced, _ = apply_fence(policy, start, params)
    hi = jnp.maximum(jnp.asarray(params.base + params.size - length, jnp.int32),
                     jnp.asarray(params.base, jnp.int32))
    fenced = jnp.minimum(fenced, hi)
    return jax.lax.dynamic_slice_in_dim(arena, fenced, length, axis=0)


def guarded_dynamic_update_slice(
    arena: jax.Array,
    start: jax.Array,
    values: jax.Array,
    params: FenceParams,
    policy: FencePolicy = FencePolicy.BITWISE,
) -> jax.Array:
    fenced, _ = apply_fence(policy, start, params)
    length = values.shape[0]
    hi = jnp.maximum(jnp.asarray(params.base + params.size - length, jnp.int32),
                     jnp.asarray(params.base, jnp.int32))
    fenced = jnp.minimum(fenced, hi)
    return jax.lax.dynamic_update_slice_in_dim(arena, values, fenced, axis=0)
