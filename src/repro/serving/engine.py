"""Continuous-batching decode engine.

One jit'd ``decode_step`` advances all active slots in one fused step
(per-slot positions); prefill runs per admitted request and its cache is
spliced into the claimed slot.  The admission order between waiting requests
is delegated to the scheduler (CNA or FIFO) — the engine reports its current
locality domain so the scheduler can apply the paper's same-socket
preference.

Greedy sampling (argmax) keeps the engine deterministic for tests; the
sampling hook is injectable.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .batching import CountingJit, PrefillBatcher
from .kvcache import SlotCache
from .prefixindex import PrefixIndex
from .prefixkv import PrefixKVStore
from .scheduler import CNAScheduler


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int
    # pod-locality domain of the prefix/KV home.  ``None`` asks the engine to
    # derive it from the prefix index at submit (production traffic has no
    # oracle); an explicit int remains an override.
    domain: int | None = 0
    out: list = field(default_factory=list)
    submit_t: int = 0
    admit_t: int = -1             # scheduler tick the request won a slot
    finish_t: int = -1
    # prompt tokens whose KV is already cached in the home domain (set by
    # prefix-index derivation); discounts the migration stall at admission —
    # only the uncached suffix of the KV moves.
    matched_len: int = 0
    # device row of the logits that emitted the final token (the next-token
    # distribution after prompt + out[:-1]), set at retirement: what a
    # logits-level check against a reference compares
    last_logits: object = None

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new


class DecodeEngine:
    """Continuous-batching decode engine over a CNA-disciplined scheduler.

    Units, because three different quantities flow through here:

      * **ticks** — ``sim_time`` and every ``*_cost`` knob
        (``domain_switch_cost``, ``slot_migration_cost``) are simulated
        scheduler ticks; one ``step()`` is one tick plus any admission
        stalls charged that tick.  Wall-clock never enters the engine.
      * **tokens** — prompt/output lengths (``Request.prompt``,
        ``matched_len``) count tokens.
      * **positions** — ``prefill_positions`` / ``reused_positions`` count
        KV cache *positions* computed or resumed; for a given prompt these
        equal its token count, but the counters aggregate across requests
        and are the unit reuse claims are pinned in.

    Optional subsystems (all default off): ``placement`` makes the slot
    cache NUMA-homed over the scheduler's topology; ``prefix_index``
    derives ``domain=None`` homes from cached prefixes; ``prefix_kv``
    resumes prefill from stored caches, deposits retiring conversations
    back, and gives the router something to ship (``export_kv`` /
    ``import_kv``); ``batching`` routes admission through the bucketed /
    packed / AOT-warmed prefill layer (``repro.serving.batching``) — at
    most one packed prefill call per ``step()``, interleaved with running
    decode, with jit trace count bounded by the bucket count instead of
    growing with distinct prompt lengths."""

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 4,
        cache_len: int = 256,
        scheduler=None,
        eos: int | None = None,
        domain_switch_cost: int = 4,
        topology=None,
        placement=None,
        slot_migration_cost: int = 2,
        prefix_index=None,
        prefix_kv=None,
        batching: bool = False,
        pack_width: int | None = None,
        tracer=None,  # repro.obs.Tracer | None (None => zero-cost off)
        paging: bool = False,
        page_size: int = 16,
    ):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.cache_len = cache_len
        # NB: schedulers define __len__, so `scheduler or default` would
        # silently replace an *empty* scheduler — compare to None explicitly.
        if scheduler is not None and topology is not None:
            raise ValueError(
                "pass topology via the scheduler (e.g. CNAScheduler(topology=...)); "
                "an explicit scheduler's topology would silently win otherwise"
            )
        self.scheduler = scheduler if scheduler is not None else CNAScheduler(topology=topology)
        # one tracer for engine + scheduler: an engine-level tracer is shared
        # down so queue_wait spans land in the same causal tree; with none
        # anywhere, both hold the falsy NULL_TRACER and every site below is
        # a single truthiness check (the zero-cost-off contract)
        if tracer is not None:
            self.scheduler.tracer = tracer
        self.tracer = self.scheduler.tracer
        self.eos = eos
        # placement: a repro.placement policy (name or instance) making the
        # slot cache NUMA-homed over the scheduler's topology — each request's
        # slot lands in (or nearest to) its KV/prefix home domain.
        if placement is not None and self.scheduler.topology is None:
            raise ValueError("placement needs a topology (e.g. CNAScheduler(topology=...))")
        # paging: the refcounted page table under the storage tier
        # (repro.serving.paging).  Gated exactly like packed prefill — paging
        # shares pages between sequences by token identity, which is
        # byte-identity only where prefill is bitwise batch-invariant (plain
        # dense attention); recurrent/SSM/sliding-window/VLM families have no
        # pageable kv_seq axis and keep the contiguous path.
        self._paged = bool(paging)
        if paging:
            gate = getattr(model, "supports_packed_prefill", None)
            if gate is None or not gate(cache_len):
                raise ValueError(
                    "paging=True needs a plain dense-attention stack (the "
                    "same gate as packed prefill): this model family has no "
                    "pageable kv_seq axis or is not bitwise batch-invariant "
                    "— run it with the contiguous path (paging=False)"
                )
            if not (prefix_kv is None or prefix_kv is True):
                raise ValueError(
                    "paging builds its own page-backed prefix store over the "
                    "slot cache's page table; pass prefix_kv=True or omit it"
                )
            from .paging import PagedPrefixKVStore
            from .paging_jax import PagedSlotCache

            store_capacity = 16  # the PrefixKVStore default; sizes the table
            self.slots = PagedSlotCache.zeros(
                model, n_slots, cache_len, page_size=page_size,
                store_slack=store_capacity,
                topology=self.scheduler.topology if placement is not None else None,
                policy=placement if placement is not None else "nearest_spill",
                page_topology=self.scheduler.topology if placement is not None else None,
            )
            prefix_kv = PagedPrefixKVStore(
                store_capacity, table=self.slots.table, pool=self.slots.pool,
            )
        else:
            self.slots = SlotCache.zeros(
                model, n_slots, cache_len,
                topology=self.scheduler.topology if placement is not None else None,
                policy=placement if placement is not None else "nearest_spill",
            )
        if self.slots.telemetry is not None:
            self.scheduler.metrics.placement = self.slots.telemetry
        # prefix_index: a repro.serving.PrefixIndex (or True for a default
        # one) deriving req.domain from the longest cached prefix when a
        # caller submits domain=None.  It learns from actual placements, so
        # it needs the placement-aware slot cache to feed it.
        if prefix_index is True:
            prefix_index = PrefixIndex()
        if prefix_index is not None and placement is None:
            raise ValueError(
                "a prefix index needs placement=... — derived homes are "
                "learned from where the slot cache actually puts each prefix"
            )
        self.prefix_index = prefix_index
        if prefix_index is not None:
            n_domains = self.scheduler.topology.n_domains
            if prefix_index.n_domains is None:
                prefix_index.n_domains = n_domains
            elif prefix_index.n_domains != n_domains:
                raise ValueError(
                    f"prefix index spans {prefix_index.n_domains} domains but "
                    f"the topology has {n_domains}"
                )
            # bind occupancy to THIS engine's live telemetry unconditionally:
            # a warm index handed over from a retired engine must not keep
            # reading (or keeping alive, via the closure) the old engine's
            # frozen counters
            telemetry = self.slots.telemetry
            prefix_index.occupancy = lambda: telemetry.per_domain_occupancy
        # prefix_kv: a repro.serving.PrefixKVStore (or True for a default one)
        # holding prefilled caches by prompt prefix, so a prompt extending a
        # stored prefix resumes decode from it instead of re-prefilling —
        # prefill_positions counts positions actually computed.
        if prefix_kv is True:
            prefix_kv = PrefixKVStore()
        self.prefix_kv = prefix_kv
        # positions actually computed vs resumed from stored caches (counts
        # of token positions, the unit reuse claims are pinned in); and
        # retirement-time deposits made back into the store
        self.prefill_positions = 0
        self.reused_positions = 0
        self.kv_deposits = 0
        # controller-coupled shedding: with both a placement-aware slot cache
        # and an adaptive controller, wire the controller's occupancy view so
        # a saturated home domain sheds new admissions to same-group siblings
        # before nearest_spill is forced to go cross-group (repro.placement).
        ctl = self.scheduler.controller
        if ctl is not None and self.slots.telemetry is not None:
            tel = self.slots.telemetry
            # rebind unconditionally, same rationale as the prefix index
            # above: a controller reused from a retired engine must not keep
            # shedding against the old engine's frozen occupancy counters or
            # a differently-shaped topology/capacity table
            ctl.occupancy = lambda: tel.per_domain_occupancy
            ctl.shed_topology = self.scheduler.topology
            ctl.domain_capacity = self.slots.pools.domain_capacity
        self.tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self.active_req: dict[int, Request] = {}
        # simulated cost accounting: a domain switch stalls the pipe while the
        # prefix/KV home moves across DCN (the paper's remote cache miss);
        # under a hierarchical topology the stall scales with the inter-domain
        # distance (cross-pod moves cost double a same-pod move).  A slot
        # placed off its home domain additionally stalls per unit of distance
        # while the prefix/KV blocks migrate to the slot's pool.
        self.domain_switch_cost = domain_switch_cost
        self.slot_migration_cost = slot_migration_cost
        self.sim_time = 0
        # counting wrappers so compile-count tests and the serving bench can
        # pin trace budgets on either path
        self._prefill = CountingJit(model.prefill)
        self._step = CountingJit(model.decode_step)
        # batching: the bucketed/packed prefill layer.  Raises at
        # construction for archs where right-padding is not bitwise-invisible
        # (recurrent/SSM/MoE/sliding-window/VLM) — run those with it off.
        self.batcher = None
        if batching:
            self.batcher = PrefillBatcher(
                model, cache_len=cache_len, pack_width=pack_width or n_slots,
            )
            # AOT: every bucket trace compiles here, none in the serving loop
            self.batcher.warm(params, cont=self.prefix_kv is not None)

    @property
    def compile_counts(self) -> dict:
        """Jit trace counts per entry point: ``prefill``/``decode`` for the
        bare per-request paths, plus ``packed_prefill``/``cont_prefill``
        when batching is on.  The regression contract: decode traces once,
        and packed-prefill traces stay bounded by the bucket count no matter
        how many distinct prompt lengths the workload carries."""
        out = {"prefill": self._prefill.traces, "decode": self._step.traces}
        if self.batcher is not None:
            out["packed_prefill"] = self.batcher.packed.traces
            out["cont_prefill"] = self.batcher.cont.traces
        return out

    # -- admission -------------------------------------------------------------
    def submit(self, req: Request):
        """Queue ``req`` for admission.  Prompts that cannot fit the cache are
        rejected here — prefill would return ``pos > cache_len``, ``_fit``
        would silently trim the KV, and the decode write would clamp onto the
        last cache entry, corrupting it.  ``domain=None`` derives the home
        from the prefix index (longest cached prefix; explicit domains remain
        an override)."""
        if len(req.prompt) >= self.cache_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens cannot fit cache_len="
                f"{self.cache_len} (need len(prompt) < cache_len to leave "
                "room for decode); truncate the prompt or grow the cache"
            )
        derived = req.domain is None
        if self.tracer:
            self.tracer.begin(
                "request", req.rid, self.scheduler.now, prompt_len=len(req.prompt)
            )
        if req.domain is None:
            if self.prefix_index is not None:
                domain, matched = self.prefix_index.home(req.prompt)
                req.matched_len = matched
                if self.slots.telemetry is not None:
                    self.slots.telemetry.record_derived_home(matched, len(req.prompt))
            else:
                domain = None
            # a cold index (or no index at all) has no opinion: domain 0 is
            # the engine's only defensible default, and it is explicit here
            # rather than coerced deep inside SlotCache.claim
            req.domain = 0 if domain is None else domain
        if self.tracer:
            now = self.scheduler.now
            self.tracer.span(
                "home_derivation", req.rid, now, now,
                domain=req.domain, matched=req.matched_len, derived=derived,
            )
        ctl = self.scheduler.controller
        if ctl is not None and self.slots.telemetry is not None:
            shed = ctl.shed_home(req.domain)
            if shed != req.domain:
                # home saturated, a same-group sibling has headroom: re-home
                # the admission there (shed) rather than letting placement
                # spill it — the matched-prefix discount no longer applies
                # at the new home, so the charge model stays honest
                if self.tracer:
                    now = self.scheduler.now
                    self.tracer.span(
                        "shed", req.rid, now, now, home=req.domain, to=shed
                    )
                req.domain = shed
                req.matched_len = 0
                self.slots.telemetry.record_shed()
        req.submit_t = self.scheduler.now
        self.scheduler.submit(req, req.domain)

    def _claim_and_charge(self, req: Request, switch_distance: int) -> int:
        """Claim a slot for a granted request and charge its admission
        stalls (domain switch + KV migration); returns the slot."""
        slot = self.slots.claim(req.rid, req.domain)
        if self._paged:
            # fresh pages for this admission's deposits land in (or nearest
            # to) the pool the slot actually got — page placement follows
            # slot placement instead of growing its own policy
            self.prefix_kv.alloc_domain = self.slots.last_domain
        migration = self.slot_migration_cost * self.slots.last_distance
        if req.matched_len and len(req.prompt):
            # only the uncached suffix of the KV is charged for an
            # off-home placement.  Modeling assumption (the index's
            # multi-holder records make it concrete): a prefix hot enough
            # to match is replicated into every pool that recently served
            # it, so the matched run is treated as already resident where
            # the slot lands and only the per-request suffix moves.
            uncached = max(0, len(req.prompt) - req.matched_len)
            migration = migration * uncached // len(req.prompt)
        stall = self.domain_switch_cost * switch_distance + migration
        self.sim_time += stall
        if self.tracer:
            now = self.scheduler.now
            sp = self.tracer.span(
                "admit", req.rid, now, now, slot=slot, domain=req.domain,
                switch_distance=switch_distance, stall_cycles=stall,
            )
            if self.slots.last_distance:
                self.tracer.span(
                    "migrate", req.rid, now, now, parent=sp,
                    distance=self.slots.last_distance, cycles=migration,
                )
        if self.prefix_index is not None and self.slots.last_domain is not None:
            # re-home: the prefix now lives wherever placement actually
            # put it, which is where the next match should send traffic
            self.prefix_index.record(req.prompt, self.slots.last_domain)
        # one handover sample per admission: the GCR feedback signal for
        # an adaptive max_active (no-op under a static/absent cap)
        self.scheduler.observe_handover(stall)
        req.admit_t = self.scheduler.now
        return slot

    def _admit(self):
        if self.batcher is not None:
            self._admit_packed()
            return
        while self.slots.n_free and len(self.scheduler):
            req = self.scheduler.next_request()
            if req is None:
                break
            slot = self._claim_and_charge(req, self.scheduler.last_admit_distance)
            p0, r0 = self.prefill_positions, self.reused_positions
            logits, cache = self._prefill_reuse(req.prompt, req.matched_len)
            if self.tracer:
                computed = self.prefill_positions - p0
                reused = self.reused_positions - r0
                kind = "reuse" if computed == 0 else ("cont" if reused else "fresh")
                now = self.scheduler.now
                self.tracer.span(
                    "prefill", req.rid, now, now,
                    kind=kind, computed=computed, reused=reused,
                )
                self.tracer.begin("decode", req.rid, now)
            self.slots.insert(slot, cache)
            if self._paged:
                # pin the live sequence to its pages: the deposit
                # _prefill_reuse just made holds the prompt's bundle, and
                # the slot keeps one reference per page until release
                self.slots.note_sequence(slot, self.prefix_kv.bundle(req.prompt))
            tok = int(jnp.argmax(logits[0]))
            req.out.append(tok)
            self.tokens = self.tokens.at[slot, 0].set(tok)
            self.active_req[slot] = req

    def _admit_packed(self):
        """Packed admission: at most one packed prefill call (plus at most
        one continuation call when a prefix-KV store is wired) per ``step``,
        so prefill *interleaves* with running decode instead of draining the
        queue synchronously.  Grants beyond ``pack_width`` stay queued for
        the next tick.  This subsumes ``_prefill_reuse`` for the batched
        path: store hits ride the continuation pack (whole suffixes at
        seeded positions, still bitwise the from-scratch result), boundary
        plants ride the fresh pack as extra rows, and accounting
        (``prefill_positions``/``reused_positions``) charges exactly what
        the per-request path would."""
        k = min(self.slots.n_free, self.batcher.pack_width)
        if k <= 0:
            return
        reqs = self.scheduler.next_batch(k)
        if not reqs:
            return
        store = self.prefix_kv
        admitted = [
            (req, self._claim_and_charge(req, dist))
            for req, dist in zip(reqs, self.scheduler.last_batch_distances)
        ]
        fresh = []   # (req, slot, boundary-plant hint)
        cont = []    # (req, slot, matched, stored cache)
        ready = []   # (req, slot, stored logits) — whole prompt cached
        if store is None:
            fresh = [(req, slot, 0) for req, slot in admitted]
        else:
            for req, slot in admitted:
                reuse = store.longest(req.prompt)
                if reuse is not None:
                    matched, cache, logits = reuse
                    self.reused_positions += matched
                    if matched == len(req.prompt):
                        self.slots.insert(slot, cache)
                        store.put([int(t) for t in req.prompt], cache, logits)
                        if self._paged:
                            self.slots.note_sequence(slot, store.bundle(req.prompt))
                        ready.append((req, slot, logits))
                    else:
                        cont.append((req, slot, matched, cache))
                else:
                    hint = max(int(req.matched_len), store.common_run(req.prompt))
                    if hint < store.min_plant or hint > len(req.prompt):
                        hint = 0
                    fresh.append((req, slot, hint))

        assign = []  # (req, slot, device first-token scalar)
        if fresh:
            rows = [req.prompt for req, _, _ in fresh]
            # boundary plants ride the same pack as extra rows when there is
            # room; their positions are a replica of the full row's prefix,
            # so they are not charged again
            plant = []
            for req, _, hint in fresh:
                if hint and len(rows) < self.batcher.pack_width:
                    plant.append((len(rows), [int(t) for t in req.prompt[:hint]]))
                    rows.append(req.prompt[:hint])
            logits, cache = self.batcher.prefill(self.params, rows)
            nxt = jnp.argmax(logits, axis=-1)
            for i, (req, slot, _hint) in enumerate(fresh):
                self.slots.insert_row(slot, cache, i)
                self.prefill_positions += len(req.prompt)
                if self.tracer:
                    now = self.scheduler.now
                    self.tracer.span(
                        "prefill", req.rid, now, now,
                        kind="fresh", computed=len(req.prompt), reused=0,
                    )
                assign.append((req, slot, nxt[i]))
                if store is not None:
                    single = self.slots.fit_single(self.batcher.extract_row(cache, i))
                    store.put([int(t) for t in req.prompt], single, logits[i : i + 1])
                    if self._paged:
                        self.slots.note_sequence(slot, store.bundle(req.prompt))
            for i, boundary in plant:
                single = self.slots.fit_single(self.batcher.extract_row(cache, i))
                store.put(boundary, single, logits[i : i + 1])
        if cont:
            rows = [c for _, _, _, c in cont]
            suffixes = [req.prompt[matched:] for req, _, matched, _ in cont]
            logits, cache = self.batcher.continue_rows(self.params, rows, suffixes)
            nxt = jnp.argmax(logits, axis=-1)
            for i, (req, slot, matched, _c) in enumerate(cont):
                self.slots.insert_row(slot, cache, i)
                self.prefill_positions += len(req.prompt) - matched
                if self.tracer:
                    now = self.scheduler.now
                    self.tracer.span(
                        "prefill", req.rid, now, now, kind="cont",
                        computed=len(req.prompt) - matched, reused=matched,
                    )
                assign.append((req, slot, nxt[i]))
                single = self.slots.fit_single(self.batcher.extract_row(cache, i))
                store.put([int(t) for t in req.prompt], single, logits[i : i + 1])
                if self._paged:
                    self.slots.note_sequence(slot, store.bundle(req.prompt))
        for req, slot, logits in ready:
            if self.tracer:
                now = self.scheduler.now
                self.tracer.span(
                    "prefill", req.rid, now, now,
                    kind="reuse", computed=0, reused=len(req.prompt),
                )
            assign.append((req, slot, jnp.argmax(logits[0])))

        # ONE host transfer for every admitted request's first token
        toks = jax.device_get([t for _, _, t in assign]) if assign else []
        for (req, slot, _), tok in zip(assign, toks):
            tok = int(tok)
            req.out.append(tok)
            self.tokens = self.tokens.at[slot, 0].set(tok)
            self.active_req[slot] = req
            if self.tracer:
                self.tracer.begin("decode", req.rid, self.scheduler.now)

    def _prefill_reuse(self, prompt, hint_len: int = 0):
        """Prefill ``prompt``, resuming from the longest stored prefix cache
        when a ``PrefixKVStore`` is wired.  A stored prefix seeds the KV
        write position past the cached run and only the uncached suffix is
        computed (one ``decode_step`` per suffix token — the incremental form
        of prefill, so results match the from-scratch path exactly);
        ``prefill_positions`` counts positions actually computed, which is
        what makes the reuse pinnable by tests and benchmarks.

        ``hint_len`` is the prefix index's ``matched_len``: when the store
        has no entry prefix-matching this prompt but the index says the run
        ``prompt[:hint_len]`` is hot, the prefill is split at that boundary
        and the boundary cache deposited, so the *next* prompt sharing the
        run resumes from it.  (Stored keys must be exact prefixes of the
        incoming prompt; shared-system-prompt traffic diverges after the
        common run, so without the boundary entry only whole-prompt
        extensions would ever hit.)"""
        store = self.prefix_kv
        if store is None:
            logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(prompt)[None]})
            cache["pos"] = jnp.asarray(cache["pos"], jnp.int32)
            self.prefill_positions += len(prompt)
            return logits, cache
        reuse = store.longest(prompt)
        # boundary hint: the index's matched_len (what the home pool holds)
        # or the store's own longest common run against a stored key —
        # whichever sees the longer shared run.  matched_len alone misses
        # batches submitted against a cold index (homes derive at submit,
        # before any placement taught the index).
        if reuse is None:
            hint_len = max(int(hint_len), store.common_run(prompt))
            if hint_len < store.min_plant:
                hint_len = 0
        if reuse is not None:
            matched, cache, logits = reuse
            self.reused_positions += matched
        elif 0 < hint_len <= len(prompt):
            boundary = [int(t) for t in prompt[:hint_len]]
            logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(boundary)[None]})
            # deposits go through fit_single so every stored entry — and
            # every suffix decode_step below — shares one (batch=1,
            # cache_len) shape and thus one jit trace; jax arrays are
            # immutable, so entries hold references, not copies
            cache = self.slots.fit_single(cache)
            store.put(boundary, cache, logits)
            matched = hint_len
            self.prefill_positions += hint_len
        else:
            matched = 0
        if matched == 0:
            logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(prompt)[None]})
            cache = self.slots.fit_single(cache)
            self.prefill_positions += len(prompt)
        else:
            for i in range(matched, len(prompt)):
                logits, cache = self._step(
                    self.params, cache, jnp.asarray([[int(prompt[i])]], jnp.int32)
                )
            self.prefill_positions += len(prompt) - matched
        store.put([int(t) for t in prompt], cache, logits)
        return logits, cache

    # -- KV shipping (repro.router.kvship) -------------------------------------
    def export_kv(self, prompt):
        """Export the longest stored prefix cache for ``prompt`` for a
        fabric transfer -> ``(tokens, (cache, logits))`` or None when no
        ``PrefixKVStore`` is wired or nothing prefixes the prompt.  The
        bundle is immutable jax arrays (references, not copies), so an
        export costs nothing until the fabric actually moves the bytes —
        pricing that move is the router's job, not this method's."""
        if self.prefix_kv is None:
            return None
        matched = self.prefix_kv.peek(prompt)
        if matched <= 0:
            return None
        key = tuple(int(t) for t in prompt)[:matched]
        entry = self.prefix_kv.get(key)
        if entry is None:
            return None
        return key, entry

    def import_kv(self, tokens, payload) -> bool:
        """Land a shipped prefix bundle in this engine's ``PrefixKVStore``
        so the next admission of a prompt extending ``tokens`` resumes from
        it (the ordinary ``_prefill_reuse`` path — shipped and locally
        prefilled caches are indistinguishable from there on).  Refuses
        (returns False) when no store is wired or the shipped cache cannot
        fit this engine's ``cache_len``; the caller then re-prefills."""
        if self.prefix_kv is None:
            return False
        cache, logits = payload
        if len(tokens) >= self.cache_len:
            return False
        self.prefix_kv.put(list(tokens), self.slots.fit_single(cache), logits)
        return True

    def peek_match(self, prompt) -> int:
        """Tokens of ``prompt`` resumable from the prefix-KV store (0
        without one) — side-effect-free, for the router's ship pricing."""
        return self.prefix_kv.peek(prompt) if self.prefix_kv is not None else 0

    # -- federation export -----------------------------------------------------
    def summary(self, top_k: int = 8) -> dict:
        """Compact replica-state export for a fleet/router tier
        (``repro.router``): live occupancy (decoding + queued) against slot
        capacity, plus the prefix index's hottest cached prefixes.  Plain
        dict so the serving layer stays import-independent of the router."""
        return {
            "occupancy": len(self.active_req) + len(self.scheduler),
            "capacity": self.n_slots,
            "prefixes": tuple(self.prefix_index.summary(top_k))
            if self.prefix_index is not None
            else (),
        }

    # -- observability ---------------------------------------------------------
    def register_metrics(self, registry, prefix: str = "engine") -> None:
        """Register this engine's live counters — and its scheduler's (and,
        transitively, placement telemetry's) surface — into a
        ``repro.obs.MetricsRegistry`` as thin views.  Reads through; nothing
        moves, no call-site changes anywhere."""
        self.scheduler.metrics.register_into(registry, prefix=f"{prefix}_sched")
        registry.gauge(f"{prefix}_prefill_positions", fn=lambda: self.prefill_positions)
        registry.gauge(f"{prefix}_reused_positions", fn=lambda: self.reused_positions)
        registry.gauge(f"{prefix}_kv_deposits", fn=lambda: self.kv_deposits)
        registry.gauge(f"{prefix}_sim_time", fn=lambda: self.sim_time)
        registry.gauge(f"{prefix}_active_slots", fn=lambda: len(self.active_req))
        registry.gauge(f"{prefix}_queued", fn=lambda: len(self.scheduler))
        if self._paged:
            # the memory-compaction claim as scrapeable numbers:
            # pages_total / pages_shared / pages_free / kv_bytes_held
            self.slots.register_into(registry, prefix=prefix)

    # -- decode ----------------------------------------------------------------
    def step(self):
        """One engine tick: admit, one fused decode step, retire finished."""
        self.scheduler.tick()
        self._admit()
        if not self.active_req:
            self.sim_time += 1
            return
        logits, new_cache = self._step(self.params, self.slots.cache, self.tokens)
        self.slots.cache = new_cache
        self.sim_time += 1
        # next-token feedback stays on device (the whole vector replaces
        # self.tokens — inactive lanes carry garbage, but claim->insert
        # overwrites a lane before it is ever decoded); the per-slot python
        # bookkeeping below then needs exactly ONE host transfer per tick
        # instead of two device syncs per active slot.
        nxt = jnp.argmax(logits, axis=-1)
        self.tokens = nxt[:, None].astype(jnp.int32)
        nxt_host, pos_host = jax.device_get((nxt, new_cache["pos"]))
        for slot, req in list(self.active_req.items()):
            tok = int(nxt_host[slot])
            req.out.append(tok)
            hit_eos = self.eos is not None and tok == self.eos
            past_len = int(pos_host[slot]) >= self.cache_len - 1
            if req.done or hit_eos or past_len:
                req.finish_t = self.scheduler.now
                req.last_logits = logits[slot]
                deposits_before = self.kv_deposits
                if self.prefix_kv is not None:
                    # retirement-time deposit: the slot's cache now encodes
                    # prompt + out[:-1] (the final token was emitted, never
                    # fed), and this step's logits row predicts out[-1] —
                    # exactly the (tokens, cache, logits) contract the store
                    # keeps.  A conversation follow-up whose prompt extends
                    # prompt+output then resumes from here instead of
                    # re-prefilling the whole history.
                    seq = [int(t) for t in req.prompt] + [int(t) for t in req.out[:-1]]
                    pos = int(pos_host[slot])
                    if 0 < pos < self.cache_len and pos == len(seq):
                        if self._paged:
                            # the deposit shares the prompt entry's pages
                            # (the slot already pins them) and writes only
                            # the decoded suffix; home the fresh pages with
                            # the retiring slot's pool
                            self.prefix_kv.alloc_domain = self.slots.slot_domain(slot)
                        self.prefix_kv.put(
                            seq, self.slots.extract(slot), logits[slot : slot + 1]
                        )
                        self.kv_deposits += 1
                if self.prefix_index is not None:
                    # the retiring slot's pool now holds KV for the full
                    # sequence — index it before release so follow-ups that
                    # extend this conversation home to the same pool
                    dom = self.slots.slot_domain(slot)
                    if dom is not None:
                        self.prefix_index.record(
                            np.concatenate([np.asarray(req.prompt), np.asarray(req.out)]),
                            dom,
                        )
                if self.tracer:
                    now = self.scheduler.now
                    self.tracer.end(
                        self.tracer.open_span(req.rid, "decode"), now,
                        tokens=len(req.out),
                    )
                    root = self.tracer.open_span(req.rid, "request")
                    if self.kv_deposits > deposits_before:
                        self.tracer.event(root, "deposit", now)
                    self.tracer.event(root, "retire", now, slot=slot)
                    self.tracer.end(root, now)
                self.slots.release(slot)
                del self.active_req[slot]

    def run(self, requests: list[Request], max_ticks: int = 10_000) -> list[Request]:
        """Submit ``requests`` and step until all retire (or ``max_ticks``
        scheduler ticks elapse); returns the same list, outputs filled."""
        for r in requests:
            self.submit(r)
        ticks = 0
        while (len(self.scheduler) or self.active_req) and ticks < max_ticks:
            self.step()
            ticks += 1
        return requests
