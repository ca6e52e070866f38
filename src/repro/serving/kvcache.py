"""Batched KV/recurrent cache slots for continuous batching.

The engine owns one cache pytree with a slot (decode-batch) axis.  Each slot
is independently claimable; inserting a prefilled (B=1) cache into slot ``i``
is a per-leaf ``dynamic_update_slice`` on that leaf's batch axis.  The batch
axis per leaf comes from the model's ``cache_logical`` tree (the position of
the "batch" logical axis), so attention KV (B,S,kv,hd), stacked KV
(L,B,S,kv,hd), RG-LRU state (B,W), SSD state (B,H,P,N) and encdec cross-KV
are all handled uniformly.

Slot *selection* is pluggable.  The baseline keeps one heap of free slots
(lowest-first, O(log n) claim/release).  With a ``topology``, slots become
NUMA-homed: ``repro.placement`` partitions them into per-domain pools, and
``claim(owner, domain)`` places each request in (or nearest to) its KV/prefix
home domain under the configured policy, charging distance-aware migration on
misses and recording per-domain telemetry.
"""

from __future__ import annotations

import heapq

import jax
import jax.numpy as jnp


class SlotCache:
    """cache pytree + slot bookkeeping."""

    def __init__(
        self, cache, axes, n_slots: int, *, topology=None, policy="nearest_spill",
        cost_model=None,
    ):
        self.cache = cache
        self.axes = axes  # per-leaf batch-axis index (or None for pos)
        self.n_slots = n_slots
        self.owner: dict[int, object] = {}
        # distance/migration cost of the most recent claim (0 for a home hit
        # or the baseline path); the engine charges stall time from these.
        # ``last_domain`` is where the slot actually landed (None on the
        # baseline path) — the prefix index re-homes hot prefixes from it.
        self.last_distance = 0
        self.last_migration_cycles = 0
        self.last_domain = None
        # CostModel pricing telemetry's migration_cycles (None -> the
        # placement layer's TWO_SOCKET default); keep it consistent with
        # whatever model benchmarks compare those cycles against.
        self.cost_model = cost_model
        if topology is None:
            self.pools = None
            self.policy = None
            self.telemetry = None
            self._free = list(range(n_slots))  # a fresh range is a valid heap
        else:
            from repro.placement import DomainFreeLists, PlacementTelemetry, get_policy

            self.pools = DomainFreeLists(n_slots, topology)
            self.policy = get_policy(policy)
            self.telemetry = PlacementTelemetry(n_domains=self.pools.topology.n_domains)
            self._free = None

    @property
    def n_free(self) -> int:
        """Free-slot count — the O(1) check for the engine's admit loop."""
        if self.pools is not None:
            return len(self.pools)
        return len(self._free)

    @property
    def free(self) -> list[int]:
        """Free slots, ascending.  NB: a *copy* under placement; treat as
        read-only and use claim/release to mutate."""
        if self.pools is not None:
            return self.pools.free_slots()
        return sorted(self._free)

    @classmethod
    def zeros(
        cls, model, n_slots: int, cache_len: int, *, topology=None, policy="nearest_spill",
        cost_model=None,
    ):
        abs_cache = model.cache_abstract(n_slots, cache_len)
        logical = model.cache_logical(abs_cache)
        axes = jax.tree.map(
            lambda l: l.index("batch") if "batch" in l else None,
            logical,
            is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x),
        )
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abs_cache)
        cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
        axes["pos"] = None
        return cls(cache, axes, n_slots, topology=topology, policy=policy, cost_model=cost_model)

    def claim(self, owner, domain: int | None = None) -> int:
        """Claim a free slot for ``owner``.  ``domain`` is the request's
        KV/prefix home; the baseline path ignores it (lowest free slot).
        Under placement the domain is required and range-checked up front —
        the same validation ``_BaseScheduler.submit`` applies — so a bad home
        cannot masquerade as domain-0 traffic in the telemetry or surface as
        an opaque IndexError inside the pools."""
        if self.pools is not None:
            topo = self.pools.topology
            if domain is None:
                raise ValueError(
                    "claim under placement needs the request's KV/prefix home "
                    "domain (got domain=None); derive one (PrefixIndex.home) "
                    "or pass it explicitly"
                )
            if not 0 <= domain < topo.n_domains:
                raise ValueError(
                    f"domain {domain} out of range for topology "
                    f"{topo.name!r} ({topo.n_domains} domains)"
                )
            p = self.policy.place(self.pools, domain, self.cost_model)
            if p is None:
                raise IndexError("claim from an exhausted SlotCache")
            self.telemetry.record_placement(p)
            self.last_distance = p.distance
            self.last_migration_cycles = p.migration_cycles
            self.last_domain = p.slot_domain
            slot = p.slot
        else:
            if not self._free:
                raise IndexError("claim from an exhausted SlotCache")
            slot = heapq.heappop(self._free)
            self.last_distance = 0
            self.last_migration_cycles = 0
            self.last_domain = None
        self.owner[slot] = owner
        return slot

    def release(self, slot: int):
        self.owner.pop(slot, None)
        # A freed slot must not advertise a stale sequence: zeroing pos makes
        # the slot read as empty the moment it is reclaimed, so nothing can
        # attend over the previous owner's KV between claim and insert.
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        if self.pools is not None:
            self.telemetry.record_release(self.pools.release(slot))
        else:
            heapq.heappush(self._free, slot)

    @property
    def active(self) -> list[int]:
        return sorted(self.owner)

    def slot_domain(self, slot: int) -> int | None:
        """Home domain of ``slot``'s pool (None on the baseline path) — the
        domain whose free list holds the KV written into this slot."""
        if self.pools is None:
            return None
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range")
        return self.pools.slot_domain[slot]

    def fit_single(self, single_cache):
        """Pad/trim a (batch=1) prefill cache so every leaf matches this
        cache's shapes with the batch axis forced to 1.  Stored prefix caches
        (``repro.serving.prefixkv``) go through this once at deposit time so
        all of them share one shape regardless of the prompt length they were
        built from — suffix ``decode_step`` calls then hit a single jit
        trace, and ``insert`` is a no-op refit."""
        new = {}
        for key in self.cache:
            if key == "pos":
                continue
            new[key] = jax.tree.map(
                lambda dst, src, ax: src if ax is None else _fit(jnp.asarray(src), dst, ax),
                self.cache[key], single_cache[key], self.axes[key],
            )
        new["pos"] = jnp.asarray(single_cache["pos"], jnp.int32)
        return new

    def extract(self, slot: int):
        """Inverse of ``insert``: copy ``slot``'s lane out of the batched
        pytree as a standalone (batch=1) cache, ``pos`` included as the
        scalar the model's prefill emits.  Each leaf is a slice, a fresh
        array and not the slot cache's own (which the engine's tick
        donates), so the result is safe to stash (``PrefixKVStore``) or ship
        to another engine — the retirement-time deposit path uses exactly
        this."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot not in self.owner:
            # an unowned slot's lane is stale KV from its previous owner (or
            # zeros); silently handing that out as a cache let a caller
            # deposit/ship garbage under a live key — refuse instead
            raise ValueError(
                f"extract from unowned slot {slot}: claim/insert it first "
                "(released slots hold stale or zero KV)"
            )

        def take(src, ax):
            if ax is None:
                return src
            return jax.lax.dynamic_slice_in_dim(src, slot, 1, axis=ax)

        new = {}
        for key in self.cache:
            if key == "pos":
                continue
            new[key] = jax.tree.map(take, self.cache[key], self.axes[key])
        new["pos"] = self.cache["pos"][slot]
        return new

    def insert_row(self, slot: int, batched_cache, row: int):
        """Scatter lane ``row`` of another batched cache pytree (a packed
        prefill's output) into ``slot`` — per leaf, slice the source lane on
        its batch axis and ``dynamic_update_slice`` it into this cache's,
        with the same pad/trim ``_fit`` applies on the single-cache path.
        This is how a packed prefill call lands its rows in their claimed
        slots without materialising per-row intermediate caches."""

        def put(dst, src, ax):
            if ax is None:
                return dst
            lane = jax.lax.dynamic_slice_in_dim(jnp.asarray(src), row, 1, axis=ax)
            lane = _fit(lane, dst, ax)
            idx = [0] * dst.ndim
            idx[ax] = slot
            return jax.lax.dynamic_update_slice(dst, lane.astype(dst.dtype), tuple(idx))

        new = {}
        for key in self.cache:
            if key == "pos":
                continue
            new[key] = jax.tree.map(put, self.cache[key], batched_cache[key], self.axes[key])
        new["pos"] = self.cache["pos"].at[slot].set(
            jnp.asarray(batched_cache["pos"], jnp.int32)[row]
        )
        self.cache = new

    def insert(self, slot: int, single_cache):
        """Insert a (batch=1) prefill cache into ``slot``."""

        def put(dst, src, ax):
            if ax is None:
                return dst
            idx = [0] * dst.ndim
            idx[ax] = slot
            src = jnp.asarray(src)
            src = _fit(src, dst, ax)
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), tuple(idx))

        new = {}
        for key in self.cache:
            if key == "pos":
                continue
            new[key] = jax.tree.map(put, self.cache[key], single_cache[key], self.axes[key])
        new["pos"] = self.cache["pos"].at[slot].set(jnp.asarray(single_cache["pos"], jnp.int32))
        self.cache = new


def _fit(src, dst, batch_ax: int):
    """Pad/trim src so every axis matches dst (batch axis forced to 1)."""
    target = tuple(1 if i == batch_ax else s for i, s in enumerate(dst.shape))
    if src.shape == target:
        return src
    pads = [(0, max(0, t - s)) for s, t in zip(src.shape, target)]
    src = jnp.pad(src, pads)
    return src[tuple(slice(0, t) for t in target)]
