"""Multi-tenant serving engine — Guardian's spatial sharing applied to a
shared LM server, **unified with the GuardianManager launch path**.

One model, one KV pool, many mutually-untrusting tenants.  The pool's
sequence-slot space is carved into contiguous pow2 partitions by the
engine's :class:`~repro.core.manager.GuardianManager` (the same buddy
allocator, bounds table and quarantine lifecycle that fence raw kernel
launches).  The engine owns **no fence table and no row-assignment
policy of its own**:

* every prefill/decode step is registered as a *trusted kernel*
  (built in :mod:`repro.launch.steps`) and submitted as a
  :class:`~repro.core.scheduler.LaunchRequest`, enqueued and drained by
  the shared :class:`BatchedLaunchScheduler` — serving traffic and raw
  tenant launches ride one dispatch layer.  Steps **compile**: the
  manager's jitted trusted path runs each step as one device program
  (params/cache/guard are operands, never closure constants);
* the engine asks the model only through its API (``models/api.py``:
  step identity, fence sizes, prefill input, key counts) and the cache
  only through ``models/kvcache.py``'s walks over the fields each cache
  type declares, so a new model touches neither this module nor
  :mod:`repro.launch.steps`;
* N engines may **share one manager** (``manager=`` / ``name=``): their
  tenants partition one global slot space, and the scheduler coalesces
  compatible steps from different engines into one fused device step —
  the multi-engine fused decode (:func:`serve_engines` drives the
  lockstep; generations stay bit-identical to solo runs);
* per-row fence params come from :meth:`GuardianManager.fence_table`
  (bitwise rows + the MODULO magic row table), gathered through a
  tenant-id column: batch row b belongs to tenant t(b), so the slot index
  of row b is fenced with t(b)'s bounds.  Even a corrupted scheduler or a
  forged slot id can only wrap inside the owning tenant's slots;
* batch-row selection uses the scheduler's shared
  :func:`~repro.core.scheduler.round_robin_interleave` fairness policy,
  weighted by the tenants' manager-side round-robin shares;
* tenants may carry **per-tenant fence policies** (a CHECK canary beside
  MODULO production tenants): the step gathers a per-row policy-code
  column and dispatches per element (``fence.apply_fence_mixed``);
* CHECK rows attribute: their ``ok`` predicates are collected per step
  and folded into the manager's ViolationLog, so a tenant spraying
  out-of-partition slot ids is quarantined by the same
  :class:`~repro.core.quarantine.QuarantineManager` poll that polices raw
  launches — and manager-side transitions propagate *back* into the
  engine through the quarantine subscription (pending requests dropped,
  pool slots scrubbed on eviction).

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --reduced --tenants 3 --requests 6 --tokens 16 \
        --policies modulo,check
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.arena import PagePool, PoolArena
from repro.core.elastic import ElasticError, ResizeEvent
from repro.core.fence import FenceParams, FencePolicy
from repro.core.manager import GuardianManager
from repro.core.partition import OutOfArenaMemory, next_pow2
from repro.core.quarantine import QuarantinePolicy, TenantState
from repro.core.scheduler import round_robin_interleave
from repro.core.telemetry import (
    SPAN_SERVE_CALL,
    SPAN_SERVE_CYCLE,
    SPAN_SERVE_DISPATCH,
    SPAN_SERVE_FINALIZE,
    SPAN_SERVE_FINISH,
    SPAN_SERVE_GAUGES,
    SPAN_SERVE_GUARD,
    SPAN_SERVE_JOIN,
    SPAN_SERVE_LAUNCH,
    SPAN_SERVE_LEAVE,
    SPAN_SERVE_META,
    SPAN_SERVE_SUBMIT,
)
from repro.core.violations import NUM_KINDS, ViolationKind
from repro.launch.compile_cache import place_compile_cache
from repro.launch.steps import (
    build_pool_relocation_step,
    build_trusted_serve_steps,
)
from repro.models import get_model
from repro.models import kvcache as KV
from repro.models.guard import GuardSpec

#: The engine's own manager tenant: owns the scratch partition where idle
#: batch rows park (their fenced writes must never land in a tenant's
#: slots) and is the tenant id under which step launches are enqueued.
#: Engines sharing a manager suffix it (``__scratch.e1``, ...) so each
#: engine gets its own scratch partition and launch queue.
ENGINE_TENANT = "__scratch"


@dataclasses.dataclass
class Request:
    tenant: str
    rid: int
    prompt: np.ndarray
    slot: int                      # absolute slot in the shared pool
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: paged mode: the request's *virtual* page ids (allocated at join
    #: time from the tenant's page extent, freed when the row leaves)
    pages: List[int] = dataclasses.field(default_factory=list)
    #: per-request generation budget (continuous driver; None = the
    #: driver-level ``max_new_tokens``)
    max_new: Optional[int] = None
    #: earliest drain cycle this request may join (arrival-trace replay)
    arrive: int = 0


@dataclasses.dataclass
class _RunState:
    """In-flight state of one engine's run: the lockstep driver
    (:func:`serve_engines`) enqueues one step per engine per drain."""

    rows: List[Request]
    slot_ids: np.ndarray
    meta: Any                      # cache meta (pool lives on the manager)
    guard: Optional[GuardSpec]
    batch: Optional[Dict]          # prefill inputs; None once prefilled
    remaining: int
    nxt: Optional[jax.Array] = None
    #: any CHECK-policy row in this run? (policies are fixed per tenant at
    #: registration, so per-step attribution can skip entirely otherwise)
    has_check: bool = False
    #: per-step next-token arrays, kept on device until _finalize — the
    #: decode loop never syncs, tokens materialize in one transfer
    trail: List[jax.Array] = dataclasses.field(default_factory=list)
    #: decode-step LaunchRequest signature, computed once per run — the
    #: operand structure (params/cache/guard trees) is invariant across a
    #: run's decode steps, so later requests skip the pytree flatten on
    #: the scheduler hot path
    decode_sig: Optional[tuple] = None


@dataclasses.dataclass
class _ContState:
    """One engine's in-flight state under the continuous driver
    (:func:`serve_continuous`): requests join and leave the fused step at
    drain-cycle boundaries, so a finished short request's row refills
    immediately instead of idling until the batch's longest request
    completes.  All sequence bookkeeping (page tables, seq lens, budgets)
    is host-authoritative — rebuilt into the meta operand every cycle —
    because a cycle may run *both* a prefill (joiners) and a decode
    (continuers) and the two steps' returned metas each advance every
    row."""

    rows: List[Optional[Request]]      # B entries; None = idle row
    left: np.ndarray                   # (B,) tokens still to emit
    lens: np.ndarray                   # (B,) host-authoritative seq lens
    nxt: jax.Array                     # (B,) device next-token operand
    #: per-cycle (tokens (B,) device array, row-owner rids) — tokens stay
    #: on device until _cont_finalize materializes the whole trail in one
    #: transfer
    trail: List[tuple] = dataclasses.field(default_factory=list)
    #: per-step (counts (B, len(api.counters)) device array, row-owner
    #: tenants), fetched with the trail
    counts: List[tuple] = dataclasses.field(default_factory=list)
    default_new: int = 16
    cycles: int = 0
    prefills: int = 0
    decodes: int = 0
    served: List[int] = dataclasses.field(default_factory=list)
    #: (RequestSpan, drain cycle its row left) of served requests: each
    #: closes in _cont_finalize, when its tokens reach the host
    finished: List[tuple] = dataclasses.field(default_factory=list)


def make_shared_manager(n_engines: int, max_batch: int = 8,
                        policy: FencePolicy = FencePolicy.BITWISE,
                        paged: bool = False, max_len: int = 256,
                        **kw) -> GuardianManager:
    """A GuardianManager sized so ``n_engines`` engines (each with its
    scratch partition plus up to one pool's worth of tenant slots) share
    one global slot space — the multi-engine fused-decode configuration.
    A guarded shared engine always fences, even while one tenant runs
    (``standalone_fast_path=False``), so generations are bit-identical
    solo vs shared.

    ``paged=True`` sizes the slot space in *virtual pages* instead of
    sequence slots (one slot per page — see ``ServeEngine(paged=True)``):
    co-hosted paged engines carve tenant page extents out of one global
    page space backing one shared physical page pool."""
    unit = max(max_len // KV.PAGE_SIZE, 1) if paged else 1
    return GuardianManager(
        total_slots=n_engines * 2 * next_pow2(max_batch) * unit,
        policy=policy, standalone_fast_path=False, **kw)


class ServeEngine:
    """Continuous-batching (fixed-slot) multi-tenant server.

    A thin client of its :class:`GuardianManager`: request bookkeeping and
    operand marshalling live here; partitioning, fencing rows, launch
    scheduling, step compilation and quarantine all live on the manager
    side.  Pass ``manager=`` (see :func:`make_shared_manager`) to co-host
    several engines on one manager — their compatible steps fuse into one
    device step per lockstep drain (:func:`serve_engines`).

    Serve tenants may carry an SLO class (``register_tenant``'s
    ``tenant_class``): it governs elastic admission (compute watermark)
    and per-class reporting; the decode steps themselves run as trusted
    steps under the engine's scratch tenant, outside the raw-launch
    queue-age machinery.
    """

    def __init__(self, cfg, *, max_batch: int = 8, max_len: int = 256,
                 policy: FencePolicy = FencePolicy.BITWISE,
                 guard: bool = True, seed: int = 0,
                 quarantine_policy: Optional[QuarantinePolicy] = None,
                 manager: Optional[GuardianManager] = None,
                 name: Optional[str] = None,
                 telemetry: bool = True,
                 paged: bool = False,
                 max_inflight: Optional[int] = None,
                 temperature: float = 0.0,
                 top_k: int = 0):
        self.cfg = cfg
        self.api = get_model(cfg)
        self.guard_enabled = guard
        self.max_batch = max_batch
        self.max_len = max_len
        self.paged = paged
        self.max_inflight = min(max_inflight or max_batch, max_batch)
        self.temperature = temperature
        self.top_k = top_k
        if paged and not self.api.global_paged:
            raise ValueError(
                "paged serve mode needs the global paged KV layout "
                f"(dense/moe transformers); {cfg.name!r} serves from the "
                "slab layout")
        #: paged mode: virtual pages per request (= max_len / page size)
        self.pages_per_req = max(max_len // KV.PAGE_SIZE, 1)
        self.params = self.api.init(jax.random.PRNGKey(seed))
        if manager is None:
            # an engine of its own is the one engine of a shared manager,
            # under the unsuffixed scratch tenant
            manager = make_shared_manager(
                1, max_batch=max_batch, policy=policy, paged=paged,
                max_len=max_len, quarantine_policy=quarantine_policy,
                telemetry=telemetry)
            self.engine_tenant = ENGINE_TENANT
        else:
            # fencing, containment and telemetry are manager-wide
            # concerns: refuse per-engine overrides instead of silently
            # ignoring them (configure them on the shared manager)
            if (policy is not FencePolicy.BITWISE
                    or quarantine_policy is not None or not telemetry):
                raise ValueError(
                    "policy/quarantine_policy/telemetry are owned by the "
                    "shared GuardianManager; configure them on the "
                    "manager (see make_shared_manager) instead of on a "
                    "co-hosted ServeEngine")
            if name is None:
                name = "e%d" % sum(
                    1 for t in manager.bounds.tenants()
                    if t.startswith(ENGINE_TENANT))
            self.engine_tenant = f"{ENGINE_TENANT}.{name}"
        self.manager = manager
        self.policy = manager.policy
        n_slots = manager.bounds.total_slots
        # paged: parked rows only need ONE request's worth of scratch page
        # ids (they all resolve to the allocator-owned garbage page); slab:
        # a scratch slot per batch row
        scratch_slots = next_pow2(self.pages_per_req if paged else max_batch)
        # ONE pool for the manager's full slot space: the pool tensors are
        # adopted by the manager as a PoolArena (the manager is the only
        # entity with device access), so co-hosted engines of the same
        # model shape share one KV pool — globally-partitioned slot ids
        # address it directly through the shared fence table, and the
        # per-engine footprint does not grow with the engine count.
        # The pool holds the model's dtype: K and V are computed in it,
        # so a wider pool would store nothing more and move twice the bytes.
        kv_dtype = jnp.dtype(cfg.dtype)
        if paged:
            # engines with equal KV geometry and dtype share the global
            # page pool; the model's identity stays in the step names
            n_phys = next_pow2(n_slots)
            pool_key = KV.global_pool_key(cfg, n_phys, kv_dtype)
            step_key = f"{self.api.step_id}:{pool_key}"
        else:
            pool_key = step_key = (f"{self.api.step_id}:s{n_slots}:"
                                   f"l{max_len}:{kv_dtype.name}")
        self._steps = build_trusted_serve_steps(
            self.api, pool_key, step_key=step_key,
            temperature=temperature, top_k=top_k,
            counts=self.manager.telemetry.enabled)
        # A later co-hosted engine adopts the already-registered pool:
        # build its cache with a single-slot pool instead (the meta half —
        # slot ids, seq lens, page tables — is slot-count independent), so
        # the dominant allocation happens once per pool, not once per
        # engine.
        size = 1 if self._steps.pool_name in self.manager.arenas else None
        if paged:
            cache = KV.init_global_kv_cache(cfg, max_batch, max_len,
                                            size or n_phys, dtype=kv_dtype)
        else:
            cache = self.api.init_cache(max_batch, max_len, dtype=kv_dtype,
                                        slots=size or n_slots)
        pool, self._meta = KV.split_pool(cache)
        self._client = self.manager.register_tenant(self.engine_tenant,
                                                    scratch_slots)
        self._scratch = self.manager.bounds.lookup(self.engine_tenant)
        #: tenants served through THIS engine (registered or submitted
        #: here) — scopes the eviction-time pool scrub to the engine that
        #: owns the evicted tenant's rows
        self._tenants: set = set()
        self.manager.quarantine.subscribe(self._on_transition)
        # elastic resizes propagate into the serving plane: a tenant's
        # (or this engine's scratch) extent moving means its KV pool
        # slots move with it and its pending requests re-address
        self.manager.elastic.subscribe(self._on_resize)
        # idempotent: a co-hosted engine adopts the existing pool (its
        # single-slot throwaway tensors are dropped before any write)
        self._pool = self._steps.register(self.manager, pool)
        if paged and self._pool.pages is None:
            # one virt->phys allocator per pool arena, shared by every
            # co-hosted engine; virt space = the manager's page-granular
            # slot space, phys space = the pool tensor's page axis
            self._pool.pages = PagePool(n_phys, n_slots)
        #: the guard's static fences: the model's, and the page clamp of
        #: the pool (a slab's pages, or every page of the global pool)
        self._fences = {space: FenceParams(base=0, size=n)
                        for space, n in self.api.fence_sizes.items()}
        self._fences["page"] = FenceParams(
            base=0, size=next_pow2(KV.pages_per_slot(self.cache)))
        tel = self.manager.telemetry
        if paged and tel.enabled:
            # the pool as allocated, against the logical KV of one token
            # at the configuration's dtype (kv_token_cycles_live counts
            # the tokens): shapes only, no device read
            tel.registry.set_gauge("kv_pool_bytes", sum(
                leaf.nbytes for a in self.manager.arenas.values()
                if isinstance(a, PoolArena)
                for leaf in jax.tree.leaves(a.buf)))
            tel.registry.set_gauge(
                "kv_bytes_per_token",
                2 * cfg.decoder_layers * cfg.n_kv_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)
        self.rejected: List[int] = []     # rids dropped by quarantine
        self._requests: List[Request] = []
        #: rid -> RequestSpan handle (absent entirely when telemetry is
        #: off — every ledger call is None-tolerant)
        self._spans: Dict[int, Any] = {}
        self._rid = 0
        self.decode_steps = 0
        #: sampled decode steps thread a fresh PRNG key per cycle
        self._sample_key = jax.random.PRNGKey(seed ^ 0x5EED) \
            if temperature > 0 else None
        # evictions fired *during* run() must survive the run-end cache
        # commit: the committed cache is re-scrubbed from this list
        self._in_run = False
        self._pending_scrubs: List[tuple] = []

    @property
    def cache(self):
        """The engine's full cache view: the manager-owned shared pool
        joined with this engine's per-batch meta.  Assignment splits the
        value back (pool half commits to the shared arena — visible to
        every co-hosted engine)."""
        return KV.join_pool(self._pool.buf, self._meta)

    @cache.setter
    def cache(self, value):
        self._pool.buf, self._meta = KV.split_pool(value)

    # ------------------------------------------------------------------ #
    # Tenant lifecycle (all state on the manager)                        #
    # ------------------------------------------------------------------ #
    @property
    def bounds(self):
        return self.manager.bounds

    @property
    def quarantine(self):
        """The shared lifecycle driver (manager-owned)."""
        return self.manager.quarantine

    def register_tenant(self, name: str, slots: int,
                        policy: Optional[FencePolicy] = None,
                        weight: int = 1,
                        tenant_class=None):
        """Carve a pool partition for ``name``; returns the Partition.

        ``policy`` optionally overrides the engine default for this
        tenant's rows (per-row mixed fencing); ``weight`` is the tenant's
        weighted-round-robin share of batch rows; ``tenant_class`` is any
        ``GuardianManager.register_tenant`` class spec (a
        TenantClassPolicy, a TenantClass, or ``"latency_critical"`` /
        ``"best_effort"``) attaching an SLO class to the tenant.  Note
        the engine's own *launches* ride under its scratch tenant, so a
        serve tenant's class governs admission and reporting; queue-age
        SLO enforcement applies to raw-launch tenants."""
        self.manager.register_tenant(name, slots, policy=policy,
                                     weight=weight,
                                     tenant_class=tenant_class)
        self._tenants.add(name)
        part = self.manager.bounds.lookup(name)
        if self.paged:
            # the tenant's partition is a *virtual page* extent: back it
            # with physical pages now (hand-over is all-or-nothing) and
            # tell the elastic plane resizes need no copy step
            self._pool.pages.bind_extent(name, part.base, part.size)
            self.manager.elastic.mark_virtual(name)
        return part

    def quarantine_tenant(self, name: str, reason: str = "") -> List[int]:
        """Reject the tenant via the manager's quarantine (the subscription
        drops its pending requests; new submissions raise).  Returns the
        dropped request ids."""
        before = len(self.rejected)
        self.manager.quarantine.quarantine(name, reason=reason)
        return self.rejected[before:]

    def evict_tenant(self, name: str) -> None:
        """Scrub the tenant's pool slots and return its partition to the
        buddy allocator (manager-side reclamation; the subscription scrubs
        the serve pool while the bounds are still resolvable)."""
        self.manager.quarantine.evict(name)

    def readmit_tenant(self, name: str) -> None:
        self.manager.quarantine.readmit(name)

    def _on_resize(self, ev: ResizeEvent) -> None:
        """Elastic extent changes propagate into the serving plane: when
        a tenant served here (or this engine's scratch partition) moves,
        its KV/state pool slots move with it — a pool relocation step
        dispatched through the same trusted scheduler path as the
        prefill/decode steps — and its pending requests re-address.
        In-place grows change no addresses, so only the bookkeeping
        refreshes.  Data-moving resizes only fire while the engine is
        idle (the elastic manager holds during serve runs), so no staged
        guard or slot-id operand can go stale."""
        mine = ev.tenant_id in self._tenants \
            or ev.tenant_id == self.engine_tenant
        if not mine:
            return
        if self.paged:
            # zero-copy resize: the extent is virtual pages — rewrite the
            # PagePool map (bytes stay in their physical pages) and the
            # in-flight requests' virtual ids; no relocation step exists
            pages = self._pool.pages
            if ev.tenant_id != self.engine_tenant:
                if ev.moved:
                    pages.rebase_extent(ev.tenant_id, ev.new_base)
                    delta = ev.new_base - ev.old_base
                    for r in self._requests:
                        if r.tenant == ev.tenant_id and not r.done \
                                and r.pages:
                            r.pages = [p + delta for p in r.pages]
                if ev.new_size > ev.old_size:
                    pages.bind_extent(ev.tenant_id, ev.new_base,
                                      ev.new_size)
                elif ev.new_size < ev.old_size:
                    pages.shrink_extent(ev.tenant_id, ev.new_size)
            else:
                self._scratch = self.manager.bounds.lookup(
                    self.engine_tenant)
            return
        if ev.moved:
            size = min(ev.old_size, ev.new_size)
            name = (f"elastic.pool[{self._steps.pool_name}]:"
                    f"{ev.old_base}->{ev.new_base}x{size}")
            self.manager.elastic.dispatch_relocation(
                ev.tenant_id, name,
                build_pool_relocation_step(ev.old_base, ev.new_base, size),
                pool_arena=self._steps.pool_name)
            for r in self._requests:
                if r.tenant == ev.tenant_id and not r.done:
                    r.slot = ev.new_base + (r.slot - ev.old_base)
        if ev.tenant_id == self.engine_tenant:
            self._scratch = self.manager.bounds.lookup(self.engine_tenant)

    def _on_transition(self, tenant_id: str, state: TenantState) -> None:
        """Manager-side quarantine events propagate into the serving plane
        (including transitions the engine never initiated, e.g. a
        ViolationLog threshold crossing from raw-launch traffic)."""
        if tenant_id.startswith(ENGINE_TENANT):
            return
        if state is TenantState.EVICTED and tenant_id in self._tenants:
            # fires before partition reclamation: bounds still resolvable.
            # Scoped to the owning engine: only the engine that served the
            # tenant ever wrote its slots, and with a shared pool the
            # co-hosted engines' subscriptions would otherwise each repeat
            # the same whole-pool scrub.  This zeroing is the KV-leak
            # barrier — the reclaimed slots must hand over empty.
            part = self.manager.bounds.lookup(tenant_id)
            ids = range(part.base, part.end)
            if self.paged:
                # translate the virtual extent to its physical pages
                # BEFORE releasing them (the map rows zero on release),
                # then zero those pages — the KV-leak barrier for the
                # global pool
                pages = self._pool.pages
                ids = tuple(int(pages.page_map[v]) for v in ids
                            if int(pages.page_map[v]))
                pages.release_extent(tenant_id)
            if self._in_run:
                # run() holds a newer local cache that overwrites
                # self.cache at run-end (and, under donation, may have
                # consumed these very buffers) — scrub the committed
                # cache at run-end instead
                self._pending_scrubs.append(ids)
            else:
                self.cache = KV.scrub(self.cache, ids)
        if not state.admissible:
            dropped = [r.rid for r in self._requests
                       if r.tenant == tenant_id and not r.done]
            self._requests = [r for r in self._requests
                              if r.done or r.tenant != tenant_id]
            self.rejected.extend(dropped)
            tel = self.manager.telemetry
            for rid in dropped:
                tel.spans.close(self._spans.pop(rid, None), "evicted")

    def submit(self, tenant: str, prompt: np.ndarray,
               max_new: Optional[int] = None, arrive: int = 0) -> int:
        """Queue one generation request; returns the request id keyed in
        :meth:`run`'s result dict.  Raises if the tenant is quarantined.
        Claims a KV slot from the tenant's pool partition, growing it
        through the elastic control plane when hard-full.

        ``max_new`` (continuous driver) caps this request's generation
        below the driver-wide budget; ``arrive`` is the earliest drain
        cycle the request may join the batch (arrival-trace replay).  In
        paged mode no slot is claimed here — virtual pages are allocated
        when the request joins a batch row, so a queued request costs
        nothing until it runs."""
        with self.manager.telemetry.span(SPAN_SERVE_SUBMIT):
            return self._submit(tenant, prompt, max_new, arrive)

    def _submit(self, tenant: str, prompt: np.ndarray,
                max_new: Optional[int], arrive: int) -> int:
        self.manager.quarantine.check_admission(tenant, "submit")
        part = self.manager.bounds.lookup(tenant)
        # a manager-registered tenant becomes this engine's to serve (and
        # therefore to scrub on eviction) the moment it submits here
        self._tenants.add(tenant)
        if self.paged:
            rid = self._rid
            self._rid += 1
            self._requests.append(Request(
                tenant=tenant, rid=rid, prompt=np.asarray(prompt),
                slot=-1, max_new=max_new, arrive=arrive))
            tel = self.manager.telemetry
            if tel.enabled:
                tel.registry.inc("requests", tenant=tenant)
                # a future-arrival request defers its span clock: queue
                # time the trace replay asked for is not queue time the
                # system imposed (_cont_join begins it at eligibility)
                self._open_span(tenant, rid, defer=arrive > 0)
            return rid
        used = {r.slot for r in self._requests if not r.done
                and r.tenant == tenant}
        free = [s for s in range(part.base, part.end) if s not in used]
        if not free:
            # the pool partition is hard full: grow it through the
            # elastic control plane (KV pools resize with their tenant —
            # the listener moves the slots if the extent relocates) and
            # retry once
            try:
                part = self.manager.elastic.grow(tenant)
            except (ElasticError, OutOfArenaMemory):
                raise RuntimeError(f"tenant {tenant}: no free slots")
            used = {r.slot for r in self._requests if not r.done
                    and r.tenant == tenant}
            free = [s for s in range(part.base, part.end)
                    if s not in used]
            if not free:
                raise RuntimeError(f"tenant {tenant}: no free slots")
        rid = self._rid
        self._rid += 1
        self._requests.append(Request(tenant=tenant, rid=rid,
                                      prompt=np.asarray(prompt),
                                      slot=free[0]))
        tel = self.manager.telemetry
        if tel.enabled:
            tel.registry.inc("requests", tenant=tenant)
            self._open_span(tenant, rid)
        # occupancy report: the pressure tracker sees serve tenants too
        # (non-shrinkable — the engine owns slot placement)
        self.manager.elastic.pressure.observe(
            tenant, len(used) + 1, part.size)
        return rid

    def _open_span(self, tenant: str, rid: int,
                   defer: bool = False) -> None:
        """Open the request's span on the manager's ledger.  An SLO class
        on the tenant attaches its slack budget (latency-critical only —
        best-effort spans complete unbudgeted)."""
        tel = self.manager.telemetry
        if not tel.enabled:
            return
        cp = self.manager.class_policy_of(tenant)
        cls = cp.tenant_class.value if cp is not None else None
        budget = cp.queue_age_budget \
            if cp is not None and cp.is_latency_critical else None
        self._spans[rid] = tel.spans.open(tenant, rid, cls=cls,
                                          budget=budget, defer=defer)

    def withdraw(self, rid: int) -> bool:
        """Remove a queued (never-ran) request; returns True when
        withdrawn.  Refuses requests that are done, hold pool pages, or
        while a run is in flight — withdrawal is a queue operation, not a
        cancellation of device work."""
        for r in self._requests:
            if r.rid != rid:
                continue
            if r.done or r.pages or self._in_run:
                return False
            self._requests.remove(r)
            self.manager.telemetry.spans.close(
                self._spans.pop(rid, None), "withdrawn")
            return True
        return False

    # ------------------------------------------------------------------ #
    def _guard_for_rows(self, rows: List[Optional[Request]]
                        ) -> Optional[GuardSpec]:
        if not self.guard_enabled:
            return None
        table, row_of = self.manager.fence_table()
        # tenant-id column: batch row b -> fence-table row of its tenant
        # (idle rows park in the engine's scratch partition)
        cols = np.full((self.max_batch,), row_of[self.engine_tenant],
                       np.int32)
        pol = np.full((self.max_batch,), self.policy.code, np.int32)
        for i, r in enumerate(rows):
            if r is not None:
                cols[i] = row_of[r.tenant]
                pol[i] = self.manager.policy_of(r.tenant).code
        slot_params = table.gather(jnp.asarray(cols))
        # row-mixed policies only when some tenant actually diverges from
        # the engine default (the homogeneous path stays bit-identical)
        mixed = bool((pol != self.policy.code).any())
        return GuardSpec(
            policy=self.policy,
            # kv: slot extents, or in paged mode the rows' *virtual page*
            # extents (the fence-table rows ARE page extents there)
            kv=slot_params,
            state=slot_params,
            row_policy=jnp.asarray(pol) if mixed else None,
            # paged: virt->phys goes through the manager-owned map, then
            # the "page" clamp keeps even a corrupted map entry inside the
            # pool tensor.  The map is a snapshot: the host rewrites it in
            # place at later cycle boundaries (join, leave, elastic rebase)
            # while this step may still wait in the device queue, and the
            # CPU backend adopts an aligned host array without copying it,
            # so the step would read the map as it is when it runs, not as
            # it was when it was handed over
            page_map=(jnp.asarray(self._pool.pages.page_map.copy())
                      if self.paged else None),
            **self._fences,
        )

    def _select_rows(self) -> List[Request]:
        """Batch-row assignment through the scheduler's shared weighted
        round-robin fairness policy (§4.2.4).  Quarantined tenants'
        requests never occupy a row — their slots re-route to admissible
        co-tenants."""
        by_tenant: Dict[str, List[Request]] = {}
        for r in self._requests:
            if r.done:
                continue
            state = self.manager.quarantine.state_of(r.tenant)
            if state is None or state.admissible:
                by_tenant.setdefault(r.tenant, []).append(r)
        weights = {t: self.manager.weight_of(t) for t in by_tenant}
        return round_robin_interleave(by_tenant, self.max_batch,
                                      weights=weights)

    def _attribute(self, rows: List[Request],
                   slot_ids: np.ndarray) -> None:
        """Per-step CHECK attribution for the serving plane: a CHECK row
        whose slot id left its owner's partition is a detected violation.

        Computed host-side from the same bounds the in-step fence used
        (the clamp happens on device; detection must not depend on model
        internals — slot fences run inside scan-over-layers).  One GATHER
        count per offending row per step, folded into the manager's
        ViolationLog so serve traffic feeds the same QuarantineManager
        poll as raw launches."""
        if not self.guard_enabled:
            return
        for i, r in enumerate(rows):
            state = self.manager.quarantine.state_of(r.tenant)
            if state is not None and not state.admissible:
                # quarantined/evicted mid-run: the row is a lame duck —
                # its bounds/log row may already be reclaimed
                continue
            if self.manager.policy_of(r.tenant) is not FencePolicy.CHECK:
                continue
            part = self.manager.bounds.lookup(r.tenant)
            if not (part.base <= int(slot_ids[i]) < part.end):
                counts = np.zeros((NUM_KINDS,), np.int32)
                counts[int(ViolationKind.GATHER)] = 1
                self.manager.violog.add(r.tenant, counts)

    # ------------------------------------------------------------------ #
    def run(self, max_new_tokens: int = 16) -> Dict[int, List[int]]:
        """Prefill all pending, then decode until done/limit.  Every step
        is a LaunchRequest drained by the manager's scheduler.  Engines
        sharing a manager should run through :func:`serve_engines`
        (slab/lockstep) or :func:`serve_continuous` (paged) instead, so
        their steps share drains."""
        if self.paged:
            return serve_continuous([self],
                                    max_new_tokens=max_new_tokens)[0]
        return serve_engines([self], max_new_tokens=max_new_tokens)[0]

    # -- lockstep phases (driven by serve_engines) --------------------- #
    def _begin(self, max_new_tokens: int) -> Optional[_RunState]:
        rows = self._select_rows()
        if not rows:
            return None
        self._in_run = True
        tel = self.manager.telemetry
        if tel.enabled:
            # requests the wave left behind (batch full) are *held* for
            # the whole run — _finalize reverts survivors to "queue"
            picked = {r.rid for r in rows}
            for r in self._requests:
                if not r.done and r.rid not in picked:
                    tel.spans.phase(self._spans.get(r.rid), "hold")
        B = self.max_batch
        plen = max(len(r.prompt) for r in rows)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r.prompt)] = r.prompt
        slot_ids = np.full((B,), self._scratch.base, np.int32)
        for i, r in enumerate(rows):
            slot_ids[i] = r.slot
        meta = KV.reset_seq_lens(KV.with_slot_ids(self._meta,
                                                  jnp.asarray(slot_ids)))
        guard = self._guard_for_rows(rows + [None] * (B - len(rows)))
        batch = self.api.prefill_input(jnp.asarray(toks))
        has_check = any(
            self.manager.policy_of(r.tenant) is FencePolicy.CHECK
            for r in rows)
        return _RunState(rows=rows, slot_ids=slot_ids, meta=meta,
                         guard=guard, batch=batch,
                         remaining=max_new_tokens, has_check=has_check)

    def _enqueue_step(self, st: _RunState):
        """Attribute CHECK rows, then enqueue this engine's next step as a
        LaunchRequest (the request doubles as the result handle).  The
        manager drain — shared with every co-hosted engine — happens in
        :func:`serve_engines`."""
        if st.has_check:
            self._attribute(st.rows, st.slot_ids)
        tel = self.manager.telemetry
        if tel.enabled:
            name = "prefill" if st.batch is not None else "decode"
            for r in st.rows:
                tel.spans.phase(self._spans.get(r.rid), name)
        if st.batch is not None:       # prefill
            return self._client.launch_kernel(
                self._steps.prefill_name,
                args=(self.params, st.meta, st.batch, st.guard))
        st.trail.append(st.nxt)        # stays on device until _finalize
        req = self._client.launch_kernel(
            self._steps.decode_name,
            args=(self.params, st.meta, st.nxt, st.guard))
        if st.decode_sig is None:
            st.decode_sig = req.signature
        else:
            req._sig = st.decode_sig
        return req

    def _finish_step(self, st: _RunState, req) -> bool:
        """Consume the drained step's result; True while decodes remain.
        The step sampled on device — no logits, no host sync here (the
        pool half of the cache was committed by the manager)."""
        st.meta, st.nxt = req.result[:2]
        if st.batch is not None:
            st.batch = None            # prefilled; decodes follow
            return st.remaining > 0
        self.decode_steps += 1
        st.remaining -= 1
        return st.remaining > 0

    def _finalize(self, st: _RunState) -> Dict[int, List[int]]:
        self._meta = st.meta           # pool already lives on the manager
        # one transfer materializes every step's sampled tokens
        if st.trail:
            toks = np.asarray(jnp.stack(st.trail))       # (steps, B)
            for i, r in enumerate(st.rows):
                r.generated.extend(int(t) for t in toks[:, i])
        # a mid-run eviction was deferred to here: re-apply to the cache
        # we just committed (zeroing is idempotent, nothing re-registers
        # inside a single-threaded run)
        self._apply_pending_scrubs()
        # rows whose tenant was quarantined/evicted mid-run were already
        # dropped + recorded in self.rejected: they must not also be
        # reported as served (their clamped generations are discarded)
        out: Dict[int, List[int]] = {}
        tel = self.manager.telemetry
        for r in st.rows:
            state = self.manager.quarantine.state_of(r.tenant)
            if state is None or state.admissible:
                r.done = True
                out[r.rid] = r.generated
                tel.spans.close(self._spans.pop(r.rid, None), "complete")
        if tel.enabled:
            # survivors the wave held now re-queue for the next run
            for r in self._requests:
                if not r.done:
                    tel.spans.phase(self._spans.get(r.rid), "queue")
        return out

    def _apply_pending_scrubs(self) -> None:
        for ids in self._pending_scrubs:
            self.cache = KV.scrub(self.cache, ids)
        self._pending_scrubs.clear()

    # -- continuous batching (paged mode; serve_continuous drives) ----- #
    def _admissible(self, tenant: str) -> bool:
        state = self.manager.quarantine.state_of(tenant)
        return state is None or state.admissible

    def _used_pages(self, tenant: str) -> set:
        used: set = set()
        for r in self._requests:
            if r.tenant == tenant and not r.done:
                used.update(r.pages)
        return used

    def _alloc_pages(self, tenant: str) -> Optional[List[int]]:
        """``pages_per_req`` free virtual ids from the tenant's extent,
        growing through the elastic plane once when full (in paged mode a
        grow — even a relocating one — is host bookkeeping only, so it is
        safe at any drain-cycle boundary)."""
        part = self.manager.bounds.lookup(tenant)
        used = self._used_pages(tenant)
        free = [v for v in range(part.base, part.end) if v not in used]
        if len(free) < self.pages_per_req:
            try:
                part = self.manager.elastic.grow(tenant)
            except (ElasticError, OutOfArenaMemory):
                return None
            used = self._used_pages(tenant)
            free = [v for v in range(part.base, part.end)
                    if v not in used]
            if len(free) < self.pages_per_req:
                return None
        return free[:self.pages_per_req]

    def _cont_begin(self, max_new_tokens: int) -> _ContState:
        self._in_run = True
        B = self.max_batch
        return _ContState(rows=[None] * B,
                          left=np.zeros((B,), np.int64),
                          lens=np.zeros((B,), np.int64),
                          nxt=jnp.zeros((B,), jnp.int32),
                          default_new=max_new_tokens)

    def _cont_held(self, st: _ContState) -> int:
        """Rows that stay through this cycle's :meth:`_cont_leave`."""
        return sum(1 for i, r in enumerate(st.rows)
                   if r is not None and st.left[i] > 0
                   and self._admissible(r.tenant))

    def _cont_leave(self, st: _ContState) -> None:
        """Cycle boundary: rows whose request exhausted its budget (or
        whose tenant lost admissibility) leave — their virtual pages
        return to the tenant's free pool immediately.  A served request's
        span closes when its tokens reach the host (:meth:`_cont_finalize`),
        stamped with this cycle."""
        tel = self.manager.telemetry
        for i, r in enumerate(st.rows):
            if r is None:
                continue
            if not self._admissible(r.tenant):
                r.pages = []
                st.rows[i] = None
                # span already closed by _on_transition; idempotent
                tel.spans.close(self._spans.pop(r.rid, None), "evicted")
                continue
            if st.left[i] <= 0:
                r.pages = []
                r.done = True
                st.served.append(r.rid)
                st.rows[i] = None
                sp = self._spans.pop(r.rid, None)
                if sp is not None:
                    st.finished.append((sp, tel.cycle))

    def _cont_join(self, st: _ContState) -> List[int]:
        """Refill idle rows from the admission queue (FIFO, gated on the
        request's arrival cycle and page availability).  Pages are
        allocated here — a queued request costs nothing until it joins.
        Returns the joined row indices (this cycle's prefill set)."""
        active = sum(1 for r in st.rows if r is not None)
        waiting = [r for r in self._requests
                   if not r.done and not r.pages
                   and r.arrive <= st.cycles
                   and self._admissible(r.tenant)]
        tel = self.manager.telemetry
        if tel.enabled:
            # deferred spans (future-arrival submits) start their clock
            # the cycle the request becomes eligible for admission
            for r in waiting:
                tel.spans.begin(self._spans.get(r.rid))
        # latency-critical requests admit ahead of class-less /
        # best-effort peers (stable: FIFO within a class — and a no-op
        # ordering when no tenant carries a class)
        def _lc_rank(req: Request) -> int:
            cp = self.manager.class_policy_of(req.tenant)
            return 0 if cp is not None and cp.is_latency_critical else 1
        waiting.sort(key=_lc_rank)
        joiners: List[int] = []
        stalled_rids: set = set()
        wi = 0
        for i in range(self.max_batch):
            if st.rows[i] is not None or active >= self.max_inflight:
                continue
            while wi < len(waiting):
                r = waiting[wi]
                wi += 1
                pages = self._alloc_pages(r.tenant)
                if pages is None:
                    # tenant page-full: later arrivals may fit
                    stalled_rids.add(r.rid)
                    continue
                r.pages = pages
                st.rows[i] = r
                st.left[i] = r.max_new if r.max_new is not None \
                    else st.default_new
                st.lens[i] = 0
                joiners.append(i)
                active += 1
                break
        if tel.enabled and waiting:
            # attribute this cycle's wait for the left-behind requests:
            # page-pool stall > bypassed-by-LC preempt > capacity hold >
            # plain queueing
            lc_joined = any(_lc_rank(st.rows[i]) == 0 for i in joiners)
            full = active >= self.max_inflight \
                or all(row is not None for row in st.rows)
            for r in waiting:
                if r.pages:
                    continue           # joined this cycle
                sp = self._spans.get(r.rid)
                if r.rid in stalled_rids:
                    tel.spans.phase(sp, "stall")
                elif lc_joined and _lc_rank(r) == 1:
                    tel.spans.phase(sp, "preempt")
                elif full:
                    tel.spans.phase(sp, "hold")
                else:
                    tel.spans.phase(sp, "queue")
        # allocator invariant: active requests never share a page, and
        # every page stays inside its owner's virtual extent (cheap host
        # ints — this is the join/leave-churn aliasing check)
        seen: Dict[int, str] = {}
        for r in st.rows:
            if r is None:
                continue
            part = self.manager.bounds.lookup(r.tenant)
            for p in r.pages:
                assert part.base <= p < part.end, \
                    f"page {p} outside {r.tenant} extent"
                assert p not in seen, \
                    f"page {p} aliased: {seen[p]} vs {r.tenant}"
                seen[p] = r.tenant
        return joiners

    def _cont_meta(self, st: _ContState, active: set):
        """Host-authoritative meta for one step: rows in ``active`` carry
        their real page table + seq len; every other row parks on the
        engine's scratch page ids (which the PagePool maps to the
        allocator-owned garbage page — parked writes land nowhere)."""
        B, P = self.max_batch, self.pages_per_req
        scratch = [self._scratch.base + (j % self._scratch.size)
                   for j in range(P)]
        pt = np.empty((B, P), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, r in enumerate(st.rows):
            if r is not None and i in active:
                pt[i] = r.pages
                lens[i] = int(st.lens[i])
            else:
                pt[i] = scratch
        return dataclasses.replace(
            self._meta, page_table=jnp.asarray(pt),
            slot_ids=jnp.zeros((B,), jnp.int32),
            seq_lens=jnp.asarray(lens))

    def _cont_dispatch(self, st: _ContState, joiners: List[int]):
        """Enqueue this cycle's steps: one prefill covering the joiner
        rows and one decode covering the continuing rows (each step parks
        the other set on scratch pages, so their pool writes are
        disjoint and dispatch order is irrelevant).  Returns the result
        handles + row sets for :meth:`_cont_finish`."""
        continuers = [i for i, r in enumerate(st.rows)
                      if r is not None and i not in set(joiners)]
        tel = self.manager.telemetry
        rids = {}
        if tel.enabled:
            for i in joiners:
                tel.spans.phase(self._spans.get(st.rows[i].rid),
                                "prefill")
            for i in continuers:
                tel.spans.phase(self._spans.get(st.rows[i].rid),
                                "decode")
            rids = {step: " ".join(str(st.rows[i].rid) for i in rows)
                    for step, rows in (("prefill", joiners),
                                       ("decode", continuers)) if rows}
        pre_req = dec_req = None
        plen = 0
        with tel.span(SPAN_SERVE_DISPATCH, **rids):
            if joiners:
                # per-step guard: rows parked for THIS step fence to the
                # engine's scratch extent (whose virtual ids resolve to
                # the garbage page) — fencing them with their tenant's
                # extent would wrap the scratch ids INTO the tenant's
                # live pages
                with tel.span(SPAN_SERVE_GUARD):
                    guard = self._guard_for_rows(
                        [r if i in set(joiners) else None
                         for i, r in enumerate(st.rows)])
                plen = max(len(st.rows[i].prompt) for i in joiners)
                toks = np.zeros((self.max_batch, plen), np.int32)
                for i in joiners:
                    toks[i, :len(st.rows[i].prompt)] = st.rows[i].prompt
                with tel.span(SPAN_SERVE_META):
                    meta = self._cont_meta(st, set(joiners))
                batch = self.api.prefill_input(jnp.asarray(toks))
                with tel.span(SPAN_SERVE_LAUNCH):
                    pre_req = self._client.launch_kernel(
                        self._steps.prefill_name,
                        args=(self.params, meta, batch, guard))
                st.prefills += 1
                if tel.enabled:
                    tel.registry.inc("prefill_tokens_useful", sum(
                        len(st.rows[i].prompt) for i in joiners))
                    tel.registry.inc("prefill_tokens_computed",
                                     self.max_batch * plen)
                    for i in joiners:
                        self._count_keys(st.rows[i].tenant, 0, plen)
            if continuers:
                with tel.span(SPAN_SERVE_GUARD):
                    guard = self._guard_for_rows(
                        [r if i in set(continuers) else None
                         for i, r in enumerate(st.rows)])
                with tel.span(SPAN_SERVE_META):
                    meta = self._cont_meta(st, set(continuers))
                if self._sample_key is not None:
                    self._sample_key, sub = jax.random.split(
                        self._sample_key)
                    x = (st.nxt, sub)
                else:
                    x = st.nxt
                with tel.span(SPAN_SERVE_LAUNCH):
                    dec_req = self._client.launch_kernel(
                        self._steps.decode_name,
                        args=(self.params, meta, x, guard))
                st.decodes += 1
                self.decode_steps += 1
                if tel.enabled:
                    for i in continuers:
                        self._count_keys(st.rows[i].tenant,
                                         int(st.lens[i]),
                                         int(st.lens[i]) + 1)
        return pre_req, dec_req, joiners, continuers, plen

    def _count_keys(self, tenant: str, start: int, stop: int) -> None:
        """Count the keys that queries at positions [start, stop) attend
        (``ModelAPI.key_counts``)."""
        for name, n in self.api.key_counts(start, stop).items():
            self.manager.telemetry.registry.inc(name, n, tenant=tenant)

    def _cont_finish(self, st: _ContState, pre_req, dec_req,
                     joiners: List[int], continuers: List[int],
                     plen: int) -> None:
        """Merge the cycle's results on device (no host sync), record the
        emitted tokens, advance budgets/lens."""
        for req, rows in ((pre_req, joiners), (dec_req, continuers)):
            if req is not None and len(req.result) > 2:
                # the step's per-row counts stay on device, like the
                # tokens, until _cont_finalize
                st.counts.append((req.result[2], tuple(
                    st.rows[i].tenant if i in rows else None
                    for i in range(self.max_batch))))
        if pre_req is not None and dec_req is not None:
            pre_nxt = pre_req.result[1]
            dec_nxt = dec_req.result[1]
            mask = np.zeros((self.max_batch,), bool)
            mask[joiners] = True
            nxt = jnp.where(jnp.asarray(mask), pre_nxt, dec_nxt)
        elif pre_req is not None:
            nxt = pre_req.result[1]
        elif dec_req is not None:
            nxt = dec_req.result[1]
        else:
            st.cycles += 1
            return
        emitting = set(joiners) | set(continuers)
        owners = tuple(
            st.rows[i].rid if i in emitting and st.rows[i] is not None
            else None for i in range(self.max_batch))
        st.trail.append((nxt, owners))
        st.nxt = nxt
        for i in joiners:
            # the device wrote the *padded* wave length (lockstep
            # semantics: pad tokens are cached and attended)
            st.lens[i] = plen
            st.left[i] -= 1
        for i in continuers:
            st.lens[i] += 1
            st.left[i] -= 1
        st.cycles += 1

    def _cont_waiting(self, st: _ContState) -> bool:
        return any(not r.done and not r.pages
                   and self._admissible(r.tenant)
                   for r in self._requests)

    def _cont_gauges(self, st: _ContState) -> None:
        tel = self.manager.telemetry
        if not tel.enabled:
            return
        inflight: Dict[str, int] = {}
        for r in st.rows:
            if r is not None:
                inflight[r.tenant] = inflight.get(r.tenant, 0) + 1
        for t in self._tenants:
            tel.registry.set_gauge("serve_inflight",
                                   float(inflight.get(t, 0)), tenant=t)
            try:
                part = self.manager.bounds.lookup(t)
            except Exception:
                continue
            tel.registry.set_gauge(
                "page_occupancy",
                len(self._used_pages(t)) / max(part.size, 1), tenant=t)
        pages = self._pool.pages
        if pages is not None:
            tel.registry.set_gauge("pool_page_occupancy",
                                   pages.occupancy())
        # tokens the active rows hold in the pool, once per cycle: over
        # serve_cycles x kv_pool_bytes, the share of the pool in use
        tel.registry.inc("kv_token_cycles_live", int(sum(
            st.lens[i] for i, r in enumerate(st.rows) if r is not None)))

    def _cont_finalize(self, st: _ContState) -> Dict[int, List[int]]:
        self._cont_leave(st)       # the rows the last cycle finished
        self._in_run = False
        self._apply_pending_scrubs()
        if st.trail:
            # one transfer materializes every cycle's emitted tokens
            toks = np.asarray(jnp.stack([t for t, _ in st.trail]))
            by_rid = {r.rid: r for r in self._requests}
            for c, (_, owners) in enumerate(st.trail):
                for i, rid in enumerate(owners):
                    if rid is not None and rid in by_rid:
                        by_rid[rid].generated.append(int(toks[c, i]))
        # delivered: a served request's wall-clock latency ends here
        tel = self.manager.telemetry
        if st.counts:
            # no stack: a list transfers without a program of its own
            got = jax.device_get([c for c, _ in st.counts])
            per: Dict[str, np.ndarray] = {}
            for counts, (_, owners) in zip(got, st.counts):
                for i, tenant in enumerate(owners):
                    if tenant is not None:
                        per[tenant] = per.get(tenant, 0) + counts[i]
            for tenant, total in per.items():
                for name, n in zip(self.api.counters, total):
                    tel.registry.inc(name, int(n), tenant=tenant)
        st.counts.clear()
        for sp, cycle in st.finished:
            tel.spans.close(sp, "complete", cycle=cycle)
        st.finished.clear()
        by_rid = {r.rid: r for r in self._requests}
        return {rid: by_rid[rid].generated for rid in st.served
                if rid in by_rid}


def serve_engines(engines: List[ServeEngine], max_new_tokens: int = 16
                  ) -> List[Dict[int, List[int]]]:
    """Lockstep driver for engines sharing one GuardianManager: every
    active engine enqueues its next prefill/decode step, then ONE manager
    drain dispatches them — compatible steps (same model shape, same
    phase) fuse into a single compiled device step, so N engines cost one
    dispatch per lockstep instead of N.  Returns one ``rid -> tokens``
    dict per engine, in order.  A single-engine call is exactly
    ``engine.run()``."""
    if not engines:
        return []
    mgr = engines[0].manager
    if any(e.manager is not mgr for e in engines[1:]):
        raise ValueError("serve_engines needs engines sharing one "
                         "GuardianManager (see make_shared_manager)")
    if any(e.paged for e in engines):
        raise ValueError("paged engines batch per-request — drive them "
                         "with serve_continuous")
    # elastic resizes that move data defer for the whole run: the staged
    # guards / slot-id operands of in-flight steps must never go stale
    mgr.elastic.hold()
    states = [e._begin(max_new_tokens) for e in engines]
    try:
        active = [i for i, s in enumerate(states) if s is not None]
        while active:
            reqs = [(i, engines[i]._enqueue_step(states[i]))
                    for i in active]
            mgr.run_queued()
            active = [i for i, req in reqs
                      if engines[i]._finish_step(states[i], req)]
        return [engines[i]._finalize(s) if s is not None else {}
                for i, s in enumerate(states)]
    finally:
        mgr.elastic.release()
        for e in engines:
            e._in_run = False


def serve_continuous(engines: List["ServeEngine"],
                     max_new_tokens: int = 16
                     ) -> List[Dict[int, List[int]]]:
    """Per-request continuous-batching driver for *paged* engines sharing
    one GuardianManager.

    Every drain cycle each engine (1) retires rows whose request
    exhausted its budget — their virtual pages free immediately — and
    (2) refills idle rows from the admission queue (FIFO, arrival-gated,
    capped by ``max_inflight``).  A cycle with joiners dispatches a
    prefill for the joining rows *and* a decode for the continuing rows;
    the two steps park each other's rows on scratch pages (all mapping to
    the allocator-owned garbage page), so their pool writes are disjoint
    and the merged next-token vector is a single on-device ``where`` —
    the loop never syncs to the host.  All engines' steps ride ONE
    manager drain per cycle.

    Unlike the lockstep driver this one takes no elastic hold: paged
    resizes and compactions are page-table rewrites (host bookkeeping,
    zero relocation copy steps), so they are safe at every cycle
    boundary.  Returns one ``rid -> tokens`` dict per engine, in order;
    per-request generations are bit-identical to a solo lockstep run of
    the same prompt (uniform prompt padding assumed, as everywhere)."""
    if not engines:
        return []
    mgr = engines[0].manager
    if any(e.manager is not mgr for e in engines[1:]):
        raise ValueError("serve_continuous needs engines sharing one "
                         "GuardianManager (see make_shared_manager)")
    if not all(e.paged for e in engines):
        raise ValueError("serve_continuous drives paged engines; slab "
                         "engines lockstep through serve_engines")
    tel = mgr.telemetry
    with tel.span(SPAN_SERVE_CALL):
        states = [e._cont_begin(max_new_tokens) for e in engines]
        try:
            _cont_cycles(engines, states, mgr)
            with tel.span(SPAN_SERVE_FINALIZE):
                return [e._cont_finalize(st)
                        for e, st in zip(engines, states)]
        finally:
            for e in engines:
                e._in_run = False


def _cont_cycles(engines: List["ServeEngine"], states: List[_ContState],
                 mgr: GuardianManager) -> None:
    """The drain cycles of one :func:`serve_continuous` call, until no
    engine holds a row or a waiting request.  Counts ``serve_cycles`` (the
    cycles that dispatched) and the host CPU time of their bodies
    (``serve_host_cpu_ns``: thread time, so a host blocked in dispatch by
    the runtime's in-flight limit does not read as host work)."""
    tel = mgr.telemetry
    stalled = 0
    while True:
        held = sum(e._cont_held(st) for e, st in zip(engines, states))
        if not held and not any(e._cont_waiting(st)
                                for e, st in zip(engines, states)):
            return          # _cont_finalize retires the last rows
        with tel.span(SPAN_SERVE_CYCLE, cycle=tel.cycle, rows=held):
            cpu0 = time.thread_time_ns() if tel.enabled else 0
            handles = []
            dispatched = False
            eligible_waiting = False
            for e, st in zip(engines, states):
                with tel.span(SPAN_SERVE_LEAVE):
                    e._cont_leave(st)
                with tel.span(SPAN_SERVE_JOIN):
                    joiners = e._cont_join(st)
                handles.append((e, st) + e._cont_dispatch(st, joiners))
                if handles[-1][2] is not None or handles[-1][3] is not None:
                    dispatched = True
                eligible_waiting = eligible_waiting or any(
                    not r.done and not r.pages and r.arrive <= st.cycles
                    and e._admissible(r.tenant) for r in e._requests)
            # eligible requests exist but nothing could join or run for
            # several consecutive cycles: every tenant is page-full with
            # no active rows to free them — fail loudly, don't spin
            stalled = stalled + 1 if (not dispatched
                                      and eligible_waiting) else 0
            if stalled > 3:
                raise RuntimeError(
                    "serve_continuous stalled: waiting requests but no "
                    "tenant can allocate pages (extents too small?)")
            mgr.run_queued()
            for e, st, pre, dec, joiners, continuers, plen in handles:
                with tel.span(SPAN_SERVE_FINISH):
                    e._cont_finish(st, pre, dec, joiners, continuers, plen)
                with tel.span(SPAN_SERVE_GAUGES):
                    e._cont_gauges(st)
            if dispatched and tel.enabled:
                tel.registry.inc("serve_cycles")
                tel.registry.inc("serve_host_cpu_ns",
                                 time.thread_time_ns() - cpu0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--no-guard", action="store_true")
    ap.add_argument("--engines", type=int, default=1,
                    help="co-hosted engines sharing one manager; >1 "
                         "exercises the multi-engine fused decode")
    ap.add_argument("--policies", default="",
                    help="comma-separated per-tenant fence policies cycled "
                         "across tenants (e.g. 'modulo,check'); empty = "
                         "engine default (bitwise) for all")
    ap.add_argument("--classes", default="",
                    help="comma-separated per-tenant SLO classes cycled "
                         "across tenants — 'latency_critical'/'lc', "
                         "'best_effort'/'be', or '-' for class-less; "
                         "empty = class-less for all (the pre-class "
                         "behavior)")
    ap.add_argument("--bench-out", default=None,
                    help="append a `name,us_per_call,derived` bench CSV "
                         "row (per-token wall time) to this file — CI's "
                         "serve-smoke runs accumulate rows here and gate "
                         "them via benchmarks.check_regression")
    ap.add_argument("--bench-name", default="serve.smoke",
                    help="row name used with --bench-out")
    ap.add_argument("--trace-out", default=None,
                    help="write the manager's flight-recorder event trace "
                         "as Chrome/Perfetto trace_event JSON to this "
                         "path (load in ui.perfetto.dev)")
    args = ap.parse_args(argv)
    place_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.engines > 1:
        mgr = make_shared_manager(args.engines, max_batch=8)
        engines = [ServeEngine(cfg, max_batch=8, max_len=256,
                               guard=not args.no_guard, manager=mgr)
                   for _ in range(args.engines)]
    else:
        engines = [ServeEngine(cfg, max_batch=8, max_len=256,
                               guard=not args.no_guard)]
    pols = [FencePolicy(p.strip()) for p in args.policies.split(",")
            if p.strip()]
    aliases = {"lc": "latency_critical", "be": "best_effort", "-": None}
    classes = [aliases.get(c.strip(), c.strip())
               for c in args.classes.split(",") if c.strip()]
    per = max(KV.pool_slots(engines[0].cache)
              // max(args.tenants * len(engines), 1) // 2, 2)
    for e, eng in enumerate(engines):
        for t in range(args.tenants):
            pol = pols[t % len(pols)] if pols else None
            cls = classes[t % len(classes)] if classes else None
            tenant = f"tenant{t}" if len(engines) == 1 \
                else f"e{e}.tenant{t}"
            eng.register_tenant(tenant, per, policy=pol, tenant_class=cls)
            if pol is not None:
                print(f"{tenant}: policy={pol.value}")
            if cls is not None:
                print(f"{tenant}: class={cls}")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        t = i % args.tenants
        for e, eng in enumerate(engines):
            tenant = f"tenant{t}" if len(engines) == 1 \
                else f"e{e}.tenant{t}"
            prompt = rng.integers(0, cfg.vocab, size=12).astype(np.int32)
            eng.submit(tenant, prompt)
    t0 = time.time()
    outs = serve_engines(engines, max_new_tokens=args.tokens)
    dt = time.time() - t0
    for e, out in enumerate(outs):
        for rid, toks in sorted(out.items()):
            print(f"engine{e} req {rid}: {toks[:8]}...")
    st = engines[0].manager.scheduler.stats
    n_out = sum(len(o) for o in outs)
    print(f"{n_out} requests, {args.tokens} tokens each, "
          f"{dt:.2f}s total, {sum(e.decode_steps for e in engines)} "
          f"decode steps, {int(st.total_launches)} scheduler launches, "
          f"mean step width {st.mean_batch_width:.1f}")
    if args.trace_out:
        trace = engines[0].manager.telemetry.trace
        with open(args.trace_out, "w") as fh:
            fh.write(trace.to_json())
        print(f"trace: {args.trace_out} ({len(trace)} events)")
    if args.bench_out:
        # per-token wall time: the one number the serve smokes gate on.
        # Includes trace/compile (cold start) — CI compares against a
        # baseline recorded the same way, normalized by the median ratio.
        n_tokens = max(n_out * args.tokens, 1)
        us = dt / n_tokens * 1e6
        row = (f"{args.bench_name},{us:.2f},"
               f"requests={n_out};tokens={n_tokens};"
               f"launches={int(st.total_launches)};"
               f"mean_width={st.mean_batch_width:.1f}")
        with open(args.bench_out, "a") as fh:
            fh.write(row + "\n")
        print(f"bench row -> {args.bench_out}: {row}")
    return outs[0]


if __name__ == "__main__":
    main()
