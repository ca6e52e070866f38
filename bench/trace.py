"""From a profiler trace to device busy time, program attribution and the
idle-gap breakdown.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain event
lists (nanoseconds): per TPU device its ``XLA Ops`` and ``XLA Modules``
lines, and the harness's own host spans (names starting ``bench.``).  The
reduction works on those lists only, so it is tested on a small recorded
trace without a chip.

The device clock and the host clock of one trace are offset by up to a few
milliseconds.  The program's jitted entry points all appear as modules named
``jit_counted(<fingerprint>)``; the k-th of them on a device is the program
the k-th harness call span (prefill, continuation, decode) launched, since
one device executes in launch order.  ``align`` then shifts device time by
the least amount that puts no module before the span that launched it.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

PROGRAM = "jit_counted("
CALL_SPANS = ("bench.prefill", "bench.cont", "bench.decode")
WINDOW = "bench.window"


@dataclass
class Trace:
    ops: list = field(default_factory=list)        # per device: [(name, start, end)]
    modules: list = field(default_factory=list)    # per device: [(name, start, end)]
    spans: list = field(default_factory=list)      # host: [(name, start, end)]

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        fix = lambda evs: [(n, float(a), float(b)) for n, a, b in evs]
        return cls([fix(o) for o in d["ops"]], [fix(m) for m in d["modules"]], fix(d["spans"]))


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, found {paths}")
    data = ProfileData.from_file(paths[0])
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            for key, dest in (("XLA Ops", tr.ops), ("XLA Modules", tr.modules)):
                line = lines.get(key)
                dest.append([] if line is None else
                            [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        tr.spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda s: s[1])
    return tr


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def overlap(merged, a: float, b: float) -> float:
    """Length of [a, b] covered by merged disjoint intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged if y > a and x < b)


def match(modules, spans) -> list:
    """Pair the program modules of one device with the harness call spans
    that launched them, in order.  -> [(span, module)]"""
    progs = [m for m in modules if m[0].startswith(PROGRAM)]
    calls = [s for s in spans if s[0] in CALL_SPANS]
    progs.sort(key=lambda m: m[1])
    return list(zip(calls, progs))


def align(pairs) -> float:
    """Least shift (ns) to add to device time so that no module starts
    before the span that launched it."""
    return max([0.0] + [s[1] - m[1] for s, m in pairs])


@dataclass
class Reduced:
    window: tuple                 # host ns
    busy_ns: float                # union of device ops in the window (mean over devices)
    pairs: list                   # [(kind, span, module)] with module on the host clock
    shift: list                   # per device
    device_ops: list              # [(label, seconds)] top 10
    idle_gaps: list               # [(host span, seconds)] top 10
    merged: list                  # busy intervals of device 0, host clock
    spans: list


def _short(op: str) -> str:
    name = op.split(" = ", 1)[0].lstrip("%")
    return name.rstrip("0123456789.").rstrip("._-") or name


def _label_at(spans, starts, t: float) -> str:
    """Innermost harness span open at host time ``t``: spans nest (one
    thread), so it is the latest-starting one that still covers ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    for name, a, b in spans[max(0, i - 64): i + 1][::-1]:
        if a <= t <= b:
            return name
    return "outside"


def reduce(tr: Trace, top: int = 10) -> Reduced:
    if not tr.ops:
        raise ValueError("the trace holds no TPU device")
    windows = [s for s in tr.spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = window = windows[0][1:]
    busy, shifts, pairs_all, merged0 = [], [], [], None
    op_time: dict = {}
    for dev, (ops, mods) in enumerate(zip(tr.ops, tr.modules)):
        pairs = match(mods, tr.spans)
        shift = align(pairs)
        shifts.append(shift)
        merged = union([(a + shift, b + shift) for _, a, b in ops])
        busy.append(overlap(merged, w0, w1))
        if dev == 0:
            merged0 = merged
            kind = {id(m): s[0].split(".", 1)[1] for s, m in pairs}
            pairs_all = [(s[0].split(".", 1)[1], s, (m[0], m[1] + shift, m[2] + shift))
                         for s, m in pairs]
            # attribute each op to the module that holds it; an op that
            # holds the next one (a while loop around its body) is a
            # container, and its time is its body's
            mods_sorted = sorted(mods, key=lambda m: m[1])
            ops_sorted = sorted(ops, key=lambda o: (o[1], -o[2]))
            j = 0
            for k, (name, a, b) in enumerate(ops_sorted):
                if not (w0 <= a + shift <= w1):
                    continue
                if k + 1 < len(ops_sorted) and ops_sorted[k + 1][1] < b:
                    continue
                while j + 1 < len(mods_sorted) and mods_sorted[j + 1][1] <= a:
                    j += 1
                m = mods_sorted[j] if mods_sorted and mods_sorted[j][1] <= a <= mods_sorted[j][2] else None
                owner = kind.get(id(m)) if m is not None else None
                if owner is None:
                    owner = m[0].split("(", 1)[0] if m is not None else "none"
                label = f"{owner}:{_short(name)}"
                op_time[label] = op_time.get(label, 0.0) + (b - a) * 1e-9
    gaps: dict = {}
    starts = [s[1] for s in tr.spans]
    bounds = sorted({t for _, a, b in tr.spans for t in (a, b)})
    prev = w0
    for a, b in merged0 + [(w1, w1)]:
        a, b = max(a, w0), min(b, w1)
        if a > prev:
            # split the gap where a harness span opens or closes
            i, j = bisect.bisect_right(bounds, prev), bisect.bisect_left(bounds, a)
            pts = [prev] + bounds[i:j] + [a]
            for x, y in zip(pts, pts[1:]):
                label = _label_at(tr.spans, starts, (x + y) / 2)
                gaps[label] = gaps.get(label, 0.0) + (y - x) * 1e-9
        prev = max(prev, b)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window=window, busy_ns=sum(busy) / len(busy), pairs=pairs_all,
                   shift=shifts, device_ops=rank(op_time), idle_gaps=rank(gaps),
                   merged=merged0, spans=tr.spans)
