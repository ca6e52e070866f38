"""Unified observability: causal request traces + one metrics registry.

The paper's whole argument is an attribution claim — CNA wins because lock
handovers stay on-socket, and you can *count* where the cycles went.  This
package is that discipline applied to the repo itself:

  ``trace``     ``Tracer``/``Span``: causally-linked, deterministic-clock
                spans per request (``submit → home-derivation → queue-wait →
                shed → ship(price/wait/transfer) → admit → migrate →
                prefill(fresh|cont|reuse) → decode → retire``), with
                discipline-level events (``Grant``/``Shuffle``/
                ``SecondaryFlush``) attached as span events;
  ``registry``  ``MetricsRegistry``: counters, gauges, and bounded
                histograms (p50/p99 under a memory cap) that the four legacy
                stat surfaces (``SchedulerMetrics``, ``PlacementTelemetry``,
                ``RouterStats``, ``ShipStats``) register into as thin views
                — no call-site API changes;
  ``export``    JSONL trace dump, Prometheus-style text rendering, and an
                ASCII per-request flame summary;
  ``phases``    ``PhaseClock``: wall-clock totals of a host loop's tick and
                its phases (the decode engine's admit / dispatch / wait /
                retire), published as registry gauges and emitted as
                ``jax.profiler`` host spans on the device trace's clock.

The ``Tracer`` never reads a wall clock: its spans carry each layer's own
deterministic clock, so a traced run replays bit-for-bit.  ``PhaseClock``
is the one wall-clock piece; it is always on and changes no control flow.

Zero-cost-off is a hard contract: every instrumentation site guards on the
tracer's truthiness (``NULL_TRACER`` is falsy), never consumes shared RNG
streams, and never changes control flow — tracing disabled is bitwise
identical to the pre-instrumentation code, and the cross-driver grant-order
tests pin it.
"""

from .export import flame, render_prometheus, to_jsonl
from .phases import PhaseClock
from .registry import BoundedHistogram, Counter, Gauge, HistogramVector, MetricsRegistry
from .trace import NULL_TRACER, NullTracer, Span, Tracer, trace_key

__all__ = [
    "BoundedHistogram",
    "Counter",
    "Gauge",
    "HistogramVector",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "PhaseClock",
    "Span",
    "Tracer",
    "flame",
    "render_prometheus",
    "to_jsonl",
    "trace_key",
]
