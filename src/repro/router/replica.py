"""Replica wrappers and the fleet-level admission controller.

A *replica* is anything the router can steer sessions to.  The protocol is
four members — ``capacity``, ``occupancy``, ``admit(session, now)`` and
``summary(top_k, now)`` — plus three KV-shipping hooks the router uses only
when shipping is enabled: ``peek_match(prompt)`` (tokens of the prompt the
replica's store holds, side-effect-free, for pricing),
``export_kv(prompt) -> (tokens, payload) | None`` and
``import_kv(tokens, payload)``.  Implemented here for a real ``DecodeEngine``
(``EngineReplica``) and in ``repro.router.sim`` for the jax-free fleet
simulator (``SimReplica``), so the router, federation, and benchmarks run
identically over either.

``FleetController`` is the GCR feedback loop at fleet granularity: one
``repro.placement.AdaptiveController`` per replica caps how many admissions
may be in flight there, fed from observed time-to-first-token.  A replica
whose TTFT collapses (queue buildup, cold cache storms) has its cap pulled
down, which makes the router shed new sessions to siblings — the fleet
analog of restricting the active set before scalability collapses.
"""

from __future__ import annotations

import contextlib

from repro.placement import AdaptiveController

from .federation import ReplicaSummary


class FleetController:
    """Per-replica in-flight admission caps driven by TTFT samples."""

    def __init__(
        self,
        n_replicas: int,
        *,
        initial: int = 8,
        min_active: int = 1,
        max_cap: int = 1 << 30,
        controllers=None,
        **controller_kwargs,
    ) -> None:
        if n_replicas < 1:
            raise ValueError("need at least one replica")
        if controllers is not None:
            controllers = list(controllers)
            if len(controllers) != n_replicas:
                raise ValueError("need one controller per replica")
            self.controllers = controllers
        else:
            self.controllers = [
                AdaptiveController(
                    initial=initial,
                    min_active=min_active,
                    max_cap=max_cap,
                    **controller_kwargs,
                )
                for _ in range(n_replicas)
            ]
        self.inflight = [0] * n_replicas

    @property
    def n_replicas(self) -> int:
        return len(self.controllers)

    def cap(self, replica: int) -> int:
        return self.controllers[replica].cap

    @property
    def caps(self) -> list[int]:
        return [c.cap for c in self.controllers]

    def can_admit(self, replica: int) -> bool:
        return self.inflight[replica] < self.controllers[replica].cap

    def note_admit(self, replica: int) -> None:
        self.inflight[replica] += 1

    def note_finish(self, replica: int) -> None:
        if self.inflight[replica] <= 0:
            raise ValueError(f"replica {replica} has no admissions in flight")
        self.inflight[replica] -= 1

    def observe_ttft(self, replica: int, ttft) -> int:
        """Feed one time-to-first-token sample; returns the updated cap."""
        return self.controllers[replica].observe(ttft)


class EngineReplica:
    """A ``DecodeEngine`` behind the replica protocol.

    The engine must run a prefix index (that is what the summary advertises
    and what derives per-session homes inside the replica); sessions are
    submitted with ``domain=None`` so the engine's own index places them in
    its internal domains, while the router only chose the *replica*.
    """

    def __init__(self, rid: int, engine, device=None) -> None:
        if engine.prefix_index is None:
            raise ValueError(
                "EngineReplica needs an engine with a prefix index — the "
                "summary it exports to the federation comes from there"
            )
        self.rid = rid
        self.engine = engine
        # the jax device holding the engine's params and caches, when the
        # fleet spreads replicas over devices: engine steps run with it as
        # the default device, and shipped bundles land there
        self.device = device
        self._live: dict[int, tuple] = {}  # sid -> (session, request)

    @property
    def capacity(self) -> int:
        return self.engine.n_slots

    @property
    def occupancy(self) -> int:
        return len(self.engine.active_req) + len(self.engine.scheduler)

    def has_capacity(self) -> bool:
        return self.occupancy < self.capacity

    def summary(self, top_k: int, now: int) -> ReplicaSummary:
        s = self.engine.summary(top_k)
        return ReplicaSummary(
            replica=self.rid,
            t=now,
            occupancy=s["occupancy"],
            capacity=s["capacity"],
            prefixes=s["prefixes"],
        )

    def admit(self, session, now: int) -> int:
        """Submit the steered session into the engine; returns the tokens of
        the prompt this replica already holds — what re-prefill accounting
        must count.  That is the *max* of the prefix index's matched_len
        (metadata: which pool is warm) and the prefix-KV store's resumable
        run (actual prefilled bytes, including just-shipped bundles): the
        index knows nothing of imported bundles and zeroes its match on an
        intra-engine shed, so counting it alone would book shipped tokens
        as re-prefilled while the router books them as avoided."""
        from repro.serving.engine import Request

        resumable = self.engine.peek_match(session.prompt)
        req = Request(
            rid=session.sid,
            prompt=list(session.prompt),
            max_new=session.decode_len,
            domain=None,
        )
        self.engine.submit(req)
        self._live[session.sid] = (session, req)
        return max(req.matched_len, resumable)

    # -- KV shipping hooks (repro.router.kvship) -------------------------------
    def peek_match(self, prompt, now: int = 0) -> int:
        """Tokens of ``prompt`` the engine's prefix-KV store could resume
        from (0 when the engine runs no store) — ship-pricing input.
        ``now`` is part of the protocol for the sim's in-flight-transfer
        embargo; a real engine's store has no router clock to consult."""
        return self.engine.peek_match(prompt)

    def export_kv(self, prompt):
        """Export the engine's longest stored prefix cache for ``prompt``
        (``(tokens, (cache, logits))`` of immutable jax arrays, or None).
        Replicas in one fleet serve the same model, so the bundle is
        shape-compatible with any sibling's ``import_kv``."""
        return self.engine.export_kv(prompt)

    def import_kv(self, tokens, payload, ready_t: int = 0) -> bool:
        """Deposit a shipped bundle into the engine's store; the steered
        session's admission then resumes from it via the ordinary
        prefill-reuse path (counted in ``reused_positions``).  ``ready_t``
        is the sim-side delivery embargo; an in-process engine receives the
        references immediately.  False means the bundle was refused (no
        store, or it cannot fit this engine's cache) — the caller must fall
        back to re-prefill and book nothing."""
        if self.device is not None:
            import jax

            payload = jax.device_put(payload, self.device)
        return self.engine.import_kv(tokens, payload)

    def step(self) -> list[tuple]:
        """One engine tick; returns ``(session, ttft)`` pairs for sessions
        that retired this tick, their ``out`` set to the generated tokens.
        TTFT is engine-clock ticks from submit to admission plus one (the
        admission's prefill emits the first token on that following tick),
        floored at 1 — the sample the fleet controller's GCR loop consumes."""
        with self._on_device():
            self.engine.step()
        done = []
        for sid, (session, req) in list(self._live.items()):
            if req.finish_t >= 0:
                session.out = tuple(req.out)
                ttft = max(0, req.admit_t - req.submit_t) + 1
                done.append((session, ttft))
                del self._live[sid]
        return done

    def _on_device(self):
        """Make the replica's device JAX's default while the engine works, so
        the arrays it creates land beside its params instead of on device 0."""
        if self.device is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(self.device)
