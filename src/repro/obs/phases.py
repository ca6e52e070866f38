"""Wall-clock phases of a host loop, on the profiler's clock.

``PhaseClock`` is what an engine times its tick with.  ``step()`` wraps
one whole tick and ``phase(name)`` one part of it.  Each of them does two
things:

  * opens a ``jax.profiler`` host span (``<prefix>.step`` as a
    ``StepTraceAnnotation`` numbered by tick, ``<prefix>.<name>`` as a
    ``TraceAnnotation``).  The span is recorded only while a profiler
    trace runs, on the host clock the device trace is aligned with;
  * adds its ``time.perf_counter_ns()`` duration to a running integer
    total, always.

``register_into`` publishes the totals into a ``MetricsRegistry`` as flat
integer gauges (``<prefix>_ticks``, ``<prefix>_tick_ns`` and one
``<prefix>_<name>_ns`` per phase, dots turned into underscores), so a
reader subtracts two snapshots to get the time a phase took over a window.

Unlike ``Tracer``, this reads the wall clock: its totals differ from run
to run and never feed back into control flow.  jax is imported when a
clock is built, so importing ``repro.obs`` stays jax-free.
"""

from __future__ import annotations

from time import perf_counter_ns


class _Phase:
    """One named phase: a reusable context manager (one thread, never
    re-entered while open)."""

    __slots__ = ("_totals", "_key", "_annotation", "_open", "_t0")

    def __init__(self, totals: dict, key: str, annotation) -> None:
        self._totals, self._key, self._annotation = totals, key, annotation
        self._open = None
        self._t0 = 0

    def __enter__(self):
        # a profiler annotation decides whether to record when it is built,
        # so it is built on each entry, not once
        self._open = self._annotation()
        self._open.__enter__()
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._totals[self._key] += perf_counter_ns() - self._t0
        self._open.__exit__(*exc)
        self._open = None
        return False


class _Step(_Phase):
    """The whole tick, which also counts ticks."""

    __slots__ = ("_clock",)

    def __init__(self, clock: "PhaseClock", span: str, annotation) -> None:
        super().__init__(clock.ns, "tick",
                         lambda: annotation(span, step_num=clock.ticks))
        self._clock = clock

    def __exit__(self, *exc):
        self._clock.ticks += 1
        return super().__exit__(*exc)


class PhaseClock:
    """Cumulative wall time of a tick and of its named phases."""

    def __init__(self, prefix: str, phases) -> None:
        from functools import partial

        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        self.ticks = 0
        self.ns = {"tick": 0}
        self._step = _Step(self, f"{prefix}.step", StepTraceAnnotation)
        self._phases = {}
        for name in phases:
            self.ns[name] = 0
            self._phases[name] = _Phase(self.ns, name,
                                        partial(TraceAnnotation, f"{prefix}.{name}"))

    def step(self) -> _Phase:
        """Context manager around one whole tick."""
        return self._step

    def phase(self, name: str) -> _Phase:
        """Context manager around one part of a tick."""
        return self._phases[name]

    def register_into(self, registry, prefix: str) -> None:
        registry.gauge(f"{prefix}_ticks", fn=lambda: self.ticks)
        for name in self.ns:
            registry.gauge(f"{prefix}_{name.replace('.', '_')}_ns",
                           fn=lambda n=name: self.ns[n])
