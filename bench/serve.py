"""Drives the program's own serving entry points on the host clock.

``DecodeEngine.submit`` and ``DecodeEngine.step`` run in one thread, driven
by a loop from ``bench/loops/`` through ``Driver.submit``, ``tick``, ``busy``
and ``wait_until``.  Every latency is timed from when the request was due,
so a long tick delays the requests due during it and the wait counts.

The harness wraps the engine instance's admission, packed prefill,
continuation prefill and decode step, to stamp times on the host and, in a
traced run, to open ``jax.profiler.TraceAnnotation`` spans around each.  The
program's jitted entry points all carry the name ``jit_counted``; the trace
reduction tells them apart by these spans.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."


@dataclass
class Rec:
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    req: object = None
    admit: float | None = None      # start of the admission call that granted it
    tokens: list = field(default_factory=list)   # host time of each output token
    finish: float | None = None
    failed: bool = False
    client: int = -1                # closed loop: the caller that sent it


@dataclass
class Call:
    kind: str            # "prefill" | "cont" | "decode"
    t0: float
    work: list           # prefill: real row lengths; decode: cached positions per live lane


class _Wrapped:
    """A jitted entry point with a span and a record around each call; the
    wrapped object's own attributes (trace counts) read through."""

    def __init__(self, fn, on_call):
        self._fn, self._on_call = fn, on_call

    def __call__(self, *args, **kwargs):
        return self._on_call(self._fn, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class Driver:
    def __init__(self, engine, *, annotate: bool):
        import jax

        self.engine = engine
        self.clock = time.perf_counter
        self.annotate = annotate
        self._annotation = jax.profiler.TraceAnnotation
        self.recs: list[Rec] = []
        self.live: dict[int, Rec] = {}
        self.calls: list[Call] = []
        self.n_ticks = 0
        self.lateness: list[float] = []
        self._next_rid = 0
        self._wrap()

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        return self._annotation(SPAN_PREFIX + name)

    # -- instrumentation -------------------------------------------------------
    def _wrap(self):
        eng = self.engine
        admit = eng._admit

        def admitted():
            t0 = self.clock()
            before = set(eng.active_req)
            with self.span("admit"):
                admit()
            t1 = self.clock()
            for slot, req in eng.active_req.items():
                if slot not in before:
                    rec = self.live[req.rid]
                    rec.admit = t0
                    rec.tokens.extend([t1] * (len(req.out) - len(rec.tokens)))

        eng._admit = admitted

        def decode(fn, *args):
            cached = ([len(r.prompt) + len(r.out) - 1 for r in eng.active_req.values()]
                      if self.annotate else [])
            return self._call("decode", fn, cached, *args)

        eng._step = _Wrapped(eng._step, decode)
        b = eng.batcher
        if b is not None:
            b.prefill = _Wrapped(
                b.prefill, lambda fn, params, prompts: self._call(
                    "prefill", fn, [len(p) for p in prompts], params, prompts))
            b.continue_rows = _Wrapped(
                b.continue_rows, lambda fn, params, rows, suffixes: self._call(
                    "cont", fn, [len(s) for s in suffixes], params, rows, suffixes))

    def _call(self, kind, fn, work, *args):
        self.calls.append(Call(kind, self.clock(), work))
        with self.span(kind):
            return fn(*args)

    # -- traffic -----------------------------------------------------------------
    def submit(self, planned, due: float) -> Rec:
        from repro.serving.engine import Request

        now = self.clock()
        rec = Rec(self._next_rid, due, planned.prompt, planned.max_new)
        self._next_rid += 1
        rec.req = Request(rid=rec.rid, prompt=planned.prompt, max_new=planned.max_new)
        self.lateness.append(now - due)
        self.recs.append(rec)
        try:
            with self.span("submit"):
                self.engine.submit(rec.req)
        except ValueError:
            rec.failed = True
            return rec
        self.live[rec.rid] = rec
        return rec

    def busy(self) -> bool:
        return bool(len(self.engine.scheduler) or self.engine.active_req)

    def tick(self) -> list[Rec]:
        """One engine step; stamps the tokens it emitted.  -> finished recs."""
        with self.span("step"):
            self.engine.step()
        t1 = self.clock()
        self.n_ticks += 1
        done = []
        for rid, rec in list(self.live.items()):
            n = len(rec.req.out)
            if n > len(rec.tokens):
                rec.tokens.extend([t1] * (n - len(rec.tokens)))
            if rec.req.finish_t >= 0:
                rec.finish = t1
                del self.live[rid]
                done.append(rec)
        return done

    def wait_until(self, t: float):
        dt = t - self.clock()
        if dt > 0:
            with self.span("wait"):
                time.sleep(dt)

    def run(self, loop, schedule, seconds: float) -> tuple[float, float]:
        """Offer ``schedule`` for ``seconds`` through ``loop`` (a module of
        ``bench/loops/``); -> (window start, window end)."""
        t0 = self.clock()
        end = t0 + seconds
        loop.run(self, schedule, t0, end)
        return t0, end


def warm(engine, prompt_range: tuple[int, int], vocab: int):
    """Run every shape the traffic will use once, outside the window: a full
    pack into every slot (pack rows, slot indices, the decode step,
    retirement), then one request per prompt bucket the traffic reaches."""
    from repro.serving.batching import bucket_for
    from repro.serving.engine import Request

    lo, hi = prompt_range
    rng = np.random.default_rng(0)
    b = engine.batcher
    buckets = sorted({bucket_for(n, b.buckets) for n in range(lo, hi + 1)})
    waves = [[lo] * engine.n_slots] + [[min(bk, hi)] for bk in buckets]
    rid = -1
    for wave in waves:
        for n in wave:
            engine.submit(Request(rid=rid, prompt=rng.integers(0, vocab, n).astype(np.int32),
                                  max_new=2))
            rid -= 1
        while len(engine.scheduler) or engine.active_req:
            engine.step()
