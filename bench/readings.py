"""Reductions shared by the metric readers in ``bench/metrics/``."""

from __future__ import annotations

import numpy as np


def p90(values):
    return float(np.percentile(values, 90)) if len(values) else None


def ttft_s(ctx) -> list:
    """Time to first token of every request due in the window, from its due
    time; a request with no first token by the window's end counts at its
    age then."""
    out = []
    for r in ctx.due_in_window():
        if r.failed:
            continue
        first = r.tokens[0] if r.tokens else None
        out.append((first if ctx.in_window(first) else ctx.t1) - r.due)
    return out


def sched_wait_s(ctx) -> list:
    """From due time to the start of the admission that gave the request a
    slot (or to the window's end, for one still queued then)."""
    return [(r.admit if ctx.in_window(r.admit) else ctx.t1) - r.due
            for r in ctx.due_in_window() if not r.failed]


def itl_s(ctx) -> list:
    """Gaps between consecutive output tokens of one request, both in the
    window."""
    out = []
    for r in ctx.recs:
        ts = [t for t in r.tokens if ctx.in_window(t)]
        out.extend(np.diff(ts).tolist())
    return out


def output_tokens(ctx) -> int:
    return sum(1 for r in ctx.recs for t in r.tokens if ctx.in_window(t))


def programs(ctx, kinds) -> list:
    """(host call record, device seconds) of each traced program of the
    given kinds, matched in launch order."""
    if ctx.trace is None:
        return []
    out = []
    for call, (kind, _span, (_name, a, b)) in zip(ctx.calls, ctx.trace.pairs):
        if call.kind != kind:
            raise ValueError(f"trace pairing is off: host call {call.kind}, span {kind}")
        if kind in kinds:
            out.append((call, (b - a) * 1e-9))
    return out


def useful_flops(ctx) -> float | None:
    """Real prompt tokens prefilled and live lanes decoded in the window,
    times the configuration's matmul FLOPs per token."""
    tokens = 0
    seen = False
    for c in ctx.calls:
        if ctx.in_window(c.t0):
            tokens += sum(c.work) if c.kind in ("prefill", "cont") else len(c.work)
            seen = seen or c.kind == "decode"
    return tokens * ctx.dims.flops_per_token if seen else None


def mfu(ctx) -> float | None:
    """Useful FLOPs per second over the chip's peak, in percent."""
    flops = useful_flops(ctx)
    if ctx.peak is None or not flops:
        return None
    return 100.0 * flops / ctx.seconds / ctx.peak.bf16_flops
