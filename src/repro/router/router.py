"""The replica router: CNA-disciplined admission over a fleet of replicas.

The serving scheduler carried the paper's discipline to one engine's
admission queue; this module carries it one hierarchy level up.  The mapping,
at fleet granularity:

  paper                      | router tier
  ---------------------------+------------------------------------------
  lock                       | the dispatch pipe (admissions are steered
                             | one at a time; steering a different replica
                             | than the last costs setup/transfer work)
  thread                     | a queued session
  NUMA socket of a thread    | the session's *home replica* — where the
                             | federation says its prefix is warm
  socket of the lock holder  | the most recently dispatched replica
  main/secondary queues      | the same two CNA queues, reused verbatim
                             | via ``CNAScheduler`` over a replica-level
                             | ``Topology`` (replicas can be grouped into
                             | cells/pods like sockets into pods)

Sessions homed on the granted replica are "local"; others wait exactly as
the paper's remote waiters do, with the same fairness threshold bounding
starvation.  On top of the discipline the router adds what a fleet needs and
a lock does not:

  * capacity gating — a session is only dispatched when some replica has
    headroom, and at most ``FleetController.cap(r)`` admissions are in
    flight per replica (the GCR loop at fleet granularity, fed from
    time-to-first-token);
  * federation-steered homes — ``FederatedPrefixIndex.route`` assigns each
    session's home from replica summaries at submit;
  * shed-before-stall — when the granted session's home replica is
    saturated, the dispatch sheds to the nearest replica (by the replica
    topology) with headroom instead of stalling the pipe, mirroring the
    placement layer's shed-before-spill;
  * priced KV shipping (``kv_ship=``) — a dispatch whose target lacks a
    prefix some other replica still holds prices ``min(re-prefill, ship)``
    over the fabric (``repro.router.kvship``) and moves the stored bundle
    when shipping wins, so a shed stops implying a full re-prefill.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from repro.core.topology import Topology, flat, get_topology
from repro.obs import BoundedHistogram
from repro.serving.scheduler import CNAScheduler

from .federation import FederatedPrefixIndex
from .kvship import Fabric, ShipCostModel, ShipDecision
from .replica import FleetController


@dataclass
class Session:
    """One routed unit of work: a prompt plus decode budget.

    Times (``submit_t``/``dispatch_t``/``finish_t``) are router-clock ticks;
    ``matched_len``/``local_matched`` are token counts.  ``ship`` carries the
    priced KV-ship decision for this dispatch when the router ran one
    (either outcome — tests recompute the argmin from it), None when
    shipping is off or nothing was worth pricing."""

    sid: int
    prompt: tuple
    decode_len: int = 8
    submit_t: int = -1
    dispatch_t: int = -1
    finish_t: int = -1
    home: int | None = None       # federation-routed replica
    replica: int | None = None    # where it actually landed (after shedding)
    matched_len: int = 0          # federation's believed cached prefix (tokens)
    local_matched: int = 0        # target replica's actual cached prefix (tokens)
    ship: ShipDecision | None = None
    fast: bool = False            # dispatched via the fissile fast path
    out: tuple = ()               # generated tokens, once an engine replica retires it

    @property
    def stall(self) -> int:
        """Admission stall: router ticks from submit to dispatch."""
        return self.dispatch_t - self.submit_t


@dataclass
class RouterStats:
    """Router-level counters beyond the scheduler's admission metrics."""

    dispatched: int = 0
    fast_dispatches: int = 0      # fissile fast path: headroom-home grants
                                  # that skipped candidates/shed/ship pricing
    sheds: int = 0
    syncs: int = 0
    reprefill_tokens: int = 0     # prompt tokens the target replica had to
    routed_tokens: int = 0        # recompute, vs all routed prompt tokens
    local_hits: int = 0           # dispatches whose target held >=1 token
    # bounded stall reservoir: list-compatible (append/len/index/iterate)
    # but capped, so a long-running router can't leak one entry per dispatch;
    # quantiles stay exact while under the cap (every bench stays under it)
    stalls: BoundedHistogram = field(default_factory=BoundedHistogram)
    # KV shipping (repro.router.kvship); tokens in tokens, cycles in router
    # ticks.  reprefill_avoided counts prompt tokens the target would have
    # recomputed had the shipped prefix not arrived first.
    ships: int = 0
    ship_declined: int = 0        # argmin chose re-prefill (price, not failure)
    ship_failed: int = 0          # argmin chose ship, but export/import refused
    shipped_tokens: int = 0
    ship_cycles: int = 0
    reprefill_avoided: int = 0
    # page-granular shipping (ShipCostModel.page_size > 0): per-source page
    # ranges moved by planned ships; a single-source ship counts 1 segment
    ship_segments: int = 0
    # speculative pre-dispatch transfers (``prefetch=``): hottest shippable
    # prefix of a near-capacity replica moved to its likely shed target ahead
    # of any dispatch — charged to the fabric pipe, never to a session
    prefetch_ships: int = 0
    prefetch_tokens: int = 0
    # fleet victim caching (``victim_cache=``): last-fleet-copy prefixes a
    # replica evicted, re-homed to a sibling over the fabric instead of
    # silently dropping the only copy
    victim_ships: int = 0
    victim_tokens: int = 0

    @property
    def hit_rate(self) -> float:
        return self.local_hits / max(1, self.dispatched)

    @property
    def reuse_fraction(self) -> float:
        """Fraction of routed prompt tokens already cached on the replica
        that served them — the fleet-level locality number."""
        return 1.0 - self.reprefill_tokens / max(1, self.routed_tokens)

    def register_into(self, registry, prefix: str = "router") -> None:
        """Expose this surface through a ``repro.obs.MetricsRegistry`` as
        thin live views — the dataclass stays the single source of truth."""
        registry.adopt(prefix, self, props=("hit_rate", "reuse_fraction"))


class ReplicaRouter:
    """Front N replicas as top-level locality domains.

    ``replicas`` implement the replica protocol (``repro.router.replica``):
    ``capacity``, ``occupancy``, ``has_capacity()``,
    ``admit(session, now) -> matched_len`` and ``summary(top_k, now)``;
    with ``kv_ship`` enabled they additionally need the shipping hooks
    ``peek_match`` / ``export_kv`` / ``import_kv``.

    Units: the router clock (``now``, ``Session.submit_t``/``dispatch_t``,
    every ``*_cycles`` stat) counts router ticks — the same unit the fleet
    simulator's ``FleetCostModel`` charges; ``matched_len`` /
    ``*_tokens`` count prompt tokens."""

    def __init__(
        self,
        replicas,
        *,
        topology: Topology | None = None,
        fairness_threshold: int = 0xFF,
        seed: int = 0xF1EE7,
        sync_every: int = 32,
        top_k: int = 8,
        max_age: int | None = None,
        controller: FleetController | None = None,
        kv_ship: "bool | ShipCostModel | None" = None,
        prefetch: bool = False,
        prefetch_margin: int = 1,
        victim_cache: bool = False,
        fissile: bool = False,
        tracer=None,  # repro.obs.Tracer | None (None => zero-cost off)
    ) -> None:
        self.replicas = list(replicas)
        n = len(self.replicas)
        if n < 1:
            raise ValueError("need at least one replica")
        topo = get_topology(topology) if topology is not None else flat(n, "replicas")
        if topo.n_domains != n:
            raise ValueError(
                f"topology {topo.name!r} has {topo.n_domains} domains "
                f"but {n} replicas were given"
            )
        self.topology = topo
        self.federation = FederatedPrefixIndex(
            n,
            occupancy=lambda: {r: self.replicas[r].occupancy for r in range(n)},
            max_age=max_age,
        )
        # fissile: the admission discipline runs behind the fast path
        # (repro.core.discipline.FissileDiscipline) and the router gates its
        # own pipeline bypass on scheduler.fast_ready() — see dispatch_one
        self._fissile = bool(fissile)
        self.scheduler = CNAScheduler(
            fairness_threshold=fairness_threshold, seed=seed, topology=topo,
            fissile=fissile, tracer=tracer,
        )
        # one tracer for router + scheduler (NULL_TRACER when off): session
        # root spans open here, the scheduler's queue_wait spans nest inside
        self.tracer = self.scheduler.tracer
        self.fleet = (
            controller
            if controller is not None
            else FleetController(
                n, initial=max(1, max(r.capacity for r in self.replicas))
            )
        )
        if self.fleet.n_replicas != n:
            raise ValueError(
                f"controller spans {self.fleet.n_replicas} replicas, fleet has {n}"
            )
        self.sync_every = sync_every
        self.top_k = top_k
        self.stats = RouterStats()
        self._last_target = 0  # where the dispatch pipe currently points
        # kv_ship: price shipping a remote replica's stored prefix KV to the
        # dispatch target against re-prefilling it there, and take the argmin
        # (repro.router.kvship).  True -> default ShipCostModel; a
        # ShipCostModel instance sets the pricing; None/False -> off (PR 4's
        # shed-before-stall behaviour, every shed re-prefills).
        if kv_ship is True:
            kv_ship = ShipCostModel()
        self.fabric = Fabric(topo, kv_ship) if kv_ship else None
        # prefetch ships: when a replica's occupancy is within
        # ``prefetch_margin`` admissions of its cap at sync time, its hottest
        # advertised prefix is speculatively shipped to the replica a shed
        # would pick — so when the shed actually happens, the prefix is
        # already resident.  Fabric-charged (reserve), session-free.
        self.prefetch = bool(prefetch)
        self.prefetch_margin = int(prefetch_margin)
        self._prefetched: set = set()
        # fleet victim caching: replicas that expose ``set_victim_hook``
        # report evicted prefix runs here; sync() re-homes the ones no other
        # replica still holds (last fleet copy) when the price is right.
        self.victim_cache = bool(victim_cache)
        if (self.prefetch or self.victim_cache) and self.fabric is None:
            raise ValueError(
                "prefetch/victim_cache move KV over the fabric — enable "
                "kv_ship (a ShipCostModel or True) to use them"
            )
        from collections import deque

        self._victims: "deque[tuple[int, tuple]]" = deque(maxlen=32)
        if self.victim_cache:
            for rid, rep in enumerate(self.replicas):
                hook = getattr(rep, "set_victim_hook", None)
                if hook is not None:
                    hook(lambda tokens, r=rid: self._victims.append((r, tuple(tokens))))

    # -- clock -----------------------------------------------------------------
    @property
    def now(self) -> int:
        return self.scheduler.now

    @property
    def metrics(self):
        """Admission-side metrics (locality/switches/fairness) — the same
        vocabulary every other driver of the discipline reports."""
        return self.scheduler.metrics

    def tick(self) -> None:
        """Advance the router clock one tick; summaries re-sync every
        ``sync_every`` ticks (0 disables periodic sync — call ``sync()``)."""
        self.scheduler.tick()
        if self.sync_every and self.now % self.sync_every == 0:
            self.sync()

    def advance(self, now: int) -> None:
        """Tick the clock forward to ``now`` (event-driven callers)."""
        while self.now < now:
            self.tick()

    # -- summaries -------------------------------------------------------------
    def sync(self) -> None:
        """Pull a fresh summary from every replica into the federation; with
        a fabric attached this is also where the two speculative movers run
        (victims first — a re-homed victim is then visible to prefetch)."""
        for rid, rep in enumerate(self.replicas):
            self.federation.apply(rep.summary(self.top_k, self.now))
        self.stats.syncs += 1
        if self.fabric is not None:
            if self.victim_cache:
                self._drain_victims()
            if self.prefetch:
                self._prefetch()

    # -- admission -------------------------------------------------------------
    def submit(self, session: Session, home: int | None = None) -> int:
        """Home ``session`` via the federation and queue it under the CNA
        discipline; returns the home replica.  An explicit ``home`` pins the
        session instead of routing it (scripted drivers — the cross-driver
        grant-order contract — steer the discipline with exact domains)."""
        if home is None:
            home, matched = self.federation.route(session.prompt, now=self.now)
        else:
            matched = 0
        session.home, session.matched_len = home, matched
        session.submit_t = self.now
        if self.tracer:
            self.tracer.begin(
                "session", session.sid, self.now, prompt_len=len(session.prompt)
            )
            self.tracer.span(
                "home_derivation", session.sid, self.now, self.now,
                home=home, matched=matched,
            )
        self.federation.note_steered(home)
        self.scheduler.submit(session, home)
        return home

    def __len__(self) -> int:
        return len(self.scheduler)

    def _has_headroom(self, r: int) -> bool:
        return self.replicas[r].has_capacity() and self.fleet.can_admit(r)

    def dispatch_one(self) -> tuple[Session, int, int] | None:
        """Grant the next session under the CNA discipline and steer it to a
        replica; returns ``(session, replica, steer_distance)`` or None when
        the queue is empty or no replica has headroom.  ``steer_distance``
        is the replica-topology distance from the previously steered replica
        (0 when the pipe stays on the same replica) — the cost drivers
        charge for re-pointing the dispatch pipe."""
        if not len(self.scheduler):
            return None
        if self._fissile:
            peek = self.scheduler.fast_peek()
            if peek is not None and self._has_headroom(peek[1]):
                # fissile fast path: the lone uncontended session goes to its
                # own home, which has headroom — no candidate scan, no pipe
                # repoint, no shed, no ship pricing, no federation lookup.
                # The grant itself is forced (one waiter), so everything
                # skipped is bitwise-invisible to the discipline; all *real*
                # accounting (admit, fleet in-flight, stats, stall) is booked
                # exactly as on the full pipeline below.
                return self._dispatch_fast()
        candidates = [r for r in range(len(self.replicas)) if self._has_headroom(r)]
        if not candidates:
            return None
        prev = self._last_target
        if not self._has_headroom(self.scheduler.current_domain):
            # The paper's "socket of the lock holder" is where the freed
            # resource lives: point the pipe at the nearest replica with
            # headroom *before* granting, so the discipline prefers sessions
            # homed where capacity actually is.  Without this, a saturated
            # fleet keeps granting sessions homed on the just-granted (full)
            # replica and sheds nearly every dispatch — a locality-destroying
            # feedback loop.
            self.scheduler.current_domain = min(
                candidates,
                key=lambda r: (self.topology.distance(prev, r),
                               self.fleet.inflight[r], r),
            )
        session = self.scheduler.next_request()
        if session is None:
            return None
        target = session.home
        if not self._has_headroom(target):
            # shed-before-stall: nearest replica (then least inflight) with
            # headroom takes the session rather than blocking the pipe
            target = min(
                candidates,
                key=lambda r: (self.topology.distance(session.home, r),
                               self.fleet.inflight[r], r),
            )
            self.stats.sheds += 1
            if self.tracer:
                self.tracer.span(
                    "shed", session.sid, self.now, self.now,
                    home=session.home, to=target,
                    distance=self.topology.distance(session.home, target),
                )
        dist = 0 if target == prev else self.topology.distance(prev, target)
        self._last_target = target
        session.replica = target
        session.dispatch_t = self.now
        if self.tracer:
            self.tracer.span(
                "dispatch", session.sid, self.now, self.now,
                replica=target, steer_distance=dist,
            )
        session.ship = self._maybe_ship(session, target)
        # admit first: if the replica rejects (raises), the fleet controller
        # must not be left with a phantom in-flight admission nobody will
        # ever note_finish
        session.local_matched = self.replicas[target].admit(session, self.now)
        self.fleet.note_admit(target)
        self.stats.dispatched += 1
        self.stats.routed_tokens += len(session.prompt)
        self.stats.reprefill_tokens += len(session.prompt) - session.local_matched
        if session.local_matched:
            self.stats.local_hits += 1
        self.stats.stalls.append(session.stall)
        return session, target, dist

    def _dispatch_fast(self) -> tuple[Session, int, int]:
        """The fissile bypass: grant the fast-slot session straight to its
        home replica.  Caller has already confirmed ``fast_peek()`` is live
        and the home has headroom.  ``session.ship`` stays None and no
        federation/fabric state is touched — the regression tests pin that a
        headroom-home dispatch books zero phantom pricing."""
        session = self.scheduler.next_request()
        target = session.home
        prev = self._last_target
        dist = 0 if target == prev else self.topology.distance(prev, target)
        self._last_target = target
        session.replica = target
        session.dispatch_t = self.now
        session.fast = True
        if self.tracer:
            self.tracer.span(
                "dispatch", session.sid, self.now, self.now,
                replica=target, steer_distance=dist, fast=True,
            )
        session.local_matched = self.replicas[target].admit(session, self.now)
        self.fleet.note_admit(target)
        self.stats.dispatched += 1
        self.stats.fast_dispatches += 1
        self.stats.routed_tokens += len(session.prompt)
        self.stats.reprefill_tokens += len(session.prompt) - session.local_matched
        if session.local_matched:
            self.stats.local_hits += 1
        self.stats.stalls.append(session.stall)
        return session, target, dist

    def _maybe_ship(self, session: Session, target: int) -> "ShipDecision | None":
        """Price moving a remote replica's stored prefix KV to ``target``
        before admitting ``session`` there; execute the transfer when it wins
        the argmin.  Returns the decision (either outcome) or None when
        shipping is off / nothing beyond the target's own holding exists.

        Discovery runs on the federation's advertised lengths (stale-able),
        but the price uses the source's *live* store (``peek_match``) — a
        summary that over-promises must not buy fabric time.  On a ship the
        source exports its stored bundle and the target imports it before
        ``admit`` runs, so the target's ordinary prefill-reuse path finds
        the prefix as if it had computed it locally."""
        if self.fabric is None or not len(session.prompt):
            return None
        prompt = session.prompt
        local = self.replicas[target].peek_match(prompt, self.now)
        if self.fabric.cm.page_size > 0:
            # page pricing on: plan disjoint page ranges over every live
            # holder instead of picking one source
            return self._ship_paged(session, target, prompt, local)
        # source selection: longest advertised holding first, then *nearest
        # to the target* — distance multiplies the priced bytes, so between
        # equal holders the far one can flip the argmin to re-prefill and
        # lose a profitable ship; source load is irrelevant (an export
        # copies references, it does not occupy the source)
        candidates = [
            (m, r)
            for r, m in self.federation.holders(prompt, now=self.now).items()
            if r != target and m > local
        ]
        if not candidates:
            return None
        src = min(
            candidates,
            key=lambda mr: (-mr[0], self.topology.distance(mr[1], target), mr[1]),
        )[1]
        actual = self.replicas[src].peek_match(prompt, self.now)
        if actual <= local:
            return None
        d = self.fabric.price(
            prompt_len=len(prompt),
            local_matched=local,
            src_matched=actual,
            src=src,
            dst=target,
            now=self.now,
        )
        if d.choice != "ship":
            self.stats.ship_declined += 1
            self._trace_ship(session, d)
            return d
        # from here the argmin chose ship; a refusal below is a *failure*
        # (ship_failed), not a price decline, and the dispatch falls back to
        # re-prefill with d.choice untouched (executed stays False) so the
        # recorded prices still audit against the recorded choice
        exported = self.replicas[src].export_kv(prompt)
        if exported is None:        # store churned between peek and export
            self.stats.ship_failed += 1
            self._trace_ship(session, d, failed=True)
            return d
        tokens, payload = exported
        # import before booking anything: a target that refuses the bundle
        # (no store, cache_len too small) must leave no fabric reservation
        # and no phantom ship counters behind — it just re-prefills.  The
        # bundle is embargoed until the projected transfer end, which equals
        # what reserve() will book (nothing else touches the fabric between).
        if not self.replicas[target].import_kv(
            tokens, payload, ready_t=self.fabric.projected_end(self.now, d)
        ):
            self.stats.ship_failed += 1
            self._trace_ship(session, d, failed=True)
            return d
        self.fabric.reserve(self.now, d)
        d.executed = True
        self._trace_ship(session, d)
        # NB: ship effects necessarily precede admit() (the import is what
        # admit's prefill reuse must see); the headroom check above is what
        # keeps admit from raising, so an exception here means a replica
        # broke the has_capacity contract — the dispatch is already lost.
        s = self.stats
        s.ships += 1
        s.ship_segments += 1
        s.shipped_tokens += len(tokens)
        s.ship_cycles += d.ship_cycles
        s.reprefill_avoided += len(tokens) - local
        return d

    def _ship_paged(self, session: Session, target: int, prompt, local: int) -> "ShipDecision | None":
        """Page-granular multi-source ship (``ShipCostModel.page_size > 0``):
        every live holder contributes the page ranges it is nearest for
        (``kvship.plan_ship``), the whole plan is priced against re-prefill,
        and on a win each ``ShipSegment`` is executed in token order with its
        own delivery embargo (cumulative — the fabric is one serialized pipe,
        so segment *i* lands only after everything before it).

        An export hands over the source's full reference bundle — references
        are free; the *price* and the booked ``shipped_tokens`` only charge
        the pages the target does not hold, which is the page table's
        accounting (a re-imported held page costs zero bytes)."""
        advertised = [
            r
            for r, m in self.federation.holders(prompt, now=self.now).items()
            if r != target and m > local
        ]
        if not advertised:
            return None
        # live-confirm every candidate: stale advertisements must not place
        # pages on a source that can no longer export them
        holders = {}
        for r in advertised:
            m = self.replicas[r].peek_match(prompt, self.now)
            if m > local:
                holders[r] = m
        if not holders:
            return None
        d = self.fabric.price_plan(
            prompt_len=len(prompt),
            local_matched=local,
            holders=holders,
            dst=target,
            now=self.now,
        )
        if d.choice != "ship":
            self.stats.ship_declined += 1
            self._trace_ship(session, d)
            return d
        # export every segment's source before importing anything: a single
        # churned store fails the whole plan cleanly (no partial landing, no
        # fabric reservation) and the dispatch re-prefills
        exports = []
        for seg in d.segments:
            ex = self.replicas[seg.src].export_kv(prompt)
            if ex is None or len(ex[0]) < seg.end_tok:
                self.stats.ship_failed += 1
                self._trace_ship(session, d, failed=True)
                return d
            exports.append(ex)
        ready = max(self.now, self.fabric.busy_until)
        for seg, (tokens, payload) in zip(d.segments, exports):
            ready += seg.cycles  # serialized pipe: embargoes accumulate
            if not self.replicas[target].import_kv(tokens, payload, ready_t=ready):
                self.stats.ship_failed += 1
                self._trace_ship(session, d, failed=True)
                return d
        # ready now equals projected_end(now, d): sum(seg.cycles) is
        # d.ship_cycles, so the last embargo and the reservation agree
        self.fabric.reserve(self.now, d)
        d.executed = True
        self._trace_ship(session, d)
        s = self.stats
        s.ships += 1
        s.ship_segments += len(d.segments)
        s.shipped_tokens += d.tokens_to_move
        s.ship_cycles += d.ship_cycles
        s.reprefill_avoided += d.src_matched - local
        return d

    def _prefetch(self) -> None:
        """Speculative pre-dispatch shipping: for each replica within
        ``prefetch_margin`` admissions of its effective cap, move its hottest
        advertised prefix to the replica a shed from it would pick — priced
        like any ship (a congested fabric or a cold prefix declines), booked
        on the fabric, and deduped so one hot prefix is not re-shipped every
        sync.  At most one transfer per hot replica per sync keeps the
        speculation from starving real (dispatch-time) ships of the pipe."""
        cm = self.fabric.cm
        n = len(self.replicas)
        for r, rep in enumerate(self.replicas):
            cap = min(rep.capacity, self.fleet.cap(r))
            if cap <= 0 or rep.occupancy + self.prefetch_margin < cap:
                continue
            targets = [t for t in range(n) if t != r and self._has_headroom(t)]
            if not targets:
                continue
            # same key a shed uses: nearest, then least in flight
            target = min(
                targets,
                key=lambda t: (self.topology.distance(r, t), self.fleet.inflight[t], t),
            )
            for tokens, _stamp in rep.summary(1, self.now).prefixes:
                tokens = tuple(tokens)
                key = (r, target, tokens)
                if key in self._prefetched or len(tokens) < cm.min_ship_tokens:
                    continue
                local = self.replicas[target].peek_match(tokens, self.now)
                actual = rep.peek_match(tokens, self.now)
                if actual <= local:
                    continue
                d = self.fabric.price(
                    prompt_len=len(tokens), local_matched=local,
                    src_matched=actual, src=r, dst=target, now=self.now,
                )
                if d.choice != "ship":
                    continue
                exported = rep.export_kv(tokens)
                if exported is None:
                    continue
                etok, payload = exported
                if not self.replicas[target].import_kv(
                    etok, payload, ready_t=self.fabric.projected_end(self.now, d)
                ):
                    continue
                self.fabric.reserve(self.now, d)
                d.executed = True
                self._prefetched.add(key)
                if len(self._prefetched) > 1024:  # bounded dedupe memory
                    self._prefetched.clear()
                self.stats.prefetch_ships += 1
                self.stats.prefetch_tokens += d.tokens_to_move
                break

    def _drain_victims(self) -> None:
        """Re-home evicted prefix runs that were the fleet's last copy.

        Replicas with a ``set_victim_hook`` report each evicted run; at sync
        the router keeps only the ones no *other* replica still advertises
        in full, picks the sibling a shed from the evictor would pick, and
        ships there when the transfer is cheaper than the re-prefill the
        fleet would otherwise pay on the prefix's next appearance.  Runs some
        sibling still holds — or that price out — are simply dropped, which
        is exactly what happened before this path existed."""
        cm = self.fabric.cm
        n = len(self.replicas)
        while self._victims:
            src, tokens = self._victims.popleft()
            if len(tokens) < cm.min_ship_tokens or n < 2:
                continue
            held_elsewhere = any(
                r != src and m >= len(tokens)
                for r, m in self.federation.holders(tokens, now=self.now).items()
            )
            if held_elsewhere:
                continue
            target = min(
                (t for t in range(n) if t != src),
                key=lambda t: (self.topology.distance(src, t), self.fleet.inflight[t], t),
            )
            local = self.replicas[target].peek_match(tokens, self.now)
            if local >= len(tokens):
                continue
            d = self.fabric.price(
                prompt_len=len(tokens), local_matched=local,
                src_matched=len(tokens), src=src, dst=target, now=self.now,
            )
            if d.choice != "ship":
                continue
            # the evicting replica no longer holds the bytes — the hook fired
            # at eviction, so the run itself is the staged payload (the sim's
            # import derives KV from the token run; an engine without the
            # hook never reaches this path)
            if not self.replicas[target].import_kv(
                tokens, None, ready_t=self.fabric.projected_end(self.now, d)
            ):
                continue
            self.fabric.reserve(self.now, d)
            d.executed = True
            self.stats.victim_ships += 1
            self.stats.victim_tokens += d.tokens_to_move

    def _trace_ship(self, session: Session, d: ShipDecision, *, failed: bool = False) -> None:
        """Record one priced ship decision as a span (either outcome): the
        price itself as an instant child, and — when the transfer actually
        ran — ``ship.wait`` (fabric backlog) and ``ship.transfer`` (the
        reserved pipe interval, ending at ``fabric_end``) as child spans."""
        if not self.tracer:
            return
        now = self.now
        end = d.fabric_end if d.executed else now
        sp = self.tracer.span(
            "ship", session.sid, now, end,
            src=d.src, dst=d.dst, distance=d.distance, choice=d.choice,
            executed=d.executed, failed=failed,
        )
        self.tracer.span(
            "ship.price", session.sid, now, now, parent=sp,
            ship_total=d.ship_total, reprefill_cycles=d.reprefill_cycles,
            wait_cycles=d.wait_cycles, ship_cycles=d.ship_cycles,
            suffix_cycles=d.suffix_cycles, src_matched=d.src_matched,
            local_matched=d.local_matched,
        )
        if d.executed:
            start = d.fabric_end - d.ship_cycles
            self.tracer.span(
                "ship.wait", session.sid, now, start, parent=sp,
                cycles=start - now,
            )
            self.tracer.span(
                "ship.transfer", session.sid, start, d.fabric_end, parent=sp,
                cycles=d.ship_cycles, tokens=d.src_matched,
            )

    def dispatch(self) -> list[tuple[Session, int, int]]:
        """Drain dispatches until out of queue or headroom."""
        out = []
        while (d := self.dispatch_one()) is not None:
            out.append(d)
        return out

    # -- completion ------------------------------------------------------------
    def complete(self, session: Session, *, ttft: int | None = None) -> None:
        """Report a session finished on its replica; ``ttft`` (submit ->
        first token, in router-clock units) feeds the fleet controller's
        GCR loop."""
        session.finish_t = self.now
        if self.tracer:
            root = self.tracer.open_span(session.sid, "session")
            self.tracer.event(root, "retire", self.now, replica=session.replica)
            self.tracer.end(root, self.now)
        self.fleet.note_finish(session.replica)
        if ttft is not None:
            self.fleet.observe_ttft(session.replica, ttft)
