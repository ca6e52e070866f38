"""Continuous-batching prefill: bucketed, packed, AOT-warmed.

The admission discipline (the paper's CNA queues) decides *who* enters;
this layer bounds *what each entry costs*:

  * **bucketed** — prompts pad to power-of-two length buckets, so the jit
    trace count is ``len(prompt_buckets(cache_len))`` (== log2(cache_len)
    for power-of-two cache lengths) regardless of traffic, and every trace
    is compiled ahead-of-time at engine construction (``warm``) so no
    compile ever lands in the serving loop.
  * **packed** — up to ``pack_width`` prompts ride one batched
    ``prefill_packed`` call; each row scatters to its decode slot via
    ``SlotCache.insert_row``.  On the ``attn_xla`` path a packed row of a
    dense-attention stack is bitwise what the per-request ``prefill``
    returns (masked pad columns contribute exact zeros; regression-tested);
    rows of a stack with SSD layers agree with it within the reference
    tolerance (``DecoderLM.supports_packed_prefill``).
  * **continuation** — prefix-KV resumes go through ``prefill_cont`` (whole
    suffixes at seeded per-row positions) instead of one ``decode_step``
    per suffix token, and *stay* bitwise-equal to the from-scratch path.

The planning core (``prompt_buckets`` / ``bucket_for`` / ``plan_packs``) is
pure python — docs/architecture.md runs it jax-free — and the module imports
jax lazily so the dependency-light lanes can import it too.
"""

from __future__ import annotations

import functools


# ---------------------------------------------------------------------------
# planning core (pure python, jax-free)
# ---------------------------------------------------------------------------

def prompt_buckets(cache_len: int) -> list[int]:
    """Power-of-two prompt-length buckets ``[2, 4, ...]`` up to the first
    bucket covering the longest admissible prompt (``cache_len - 1``; the
    engine rejects longer ones at submit).  For a power-of-two ``cache_len``
    this is exactly ``log2(cache_len)`` buckets — the jit trace budget the
    compile-count tests and the serving bench pin."""
    if cache_len < 2:
        raise ValueError(f"cache_len {cache_len} leaves no room for a prompt")
    out, b = [], 2
    while b < cache_len - 1:
        out.append(b)
        b *= 2
    out.append(b)
    return out


def bucket_for(length: int, buckets: list[int]) -> int:
    """Smallest bucket holding ``length`` tokens."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(f"length {length} exceeds the largest bucket {buckets[-1]}")


def plan_packs(lengths, *, pack_width: int, buckets) -> list[tuple[int, list[int]]]:
    """Plan packed prefill calls over prompts of the given ``lengths``.

    Pure function of the queue snapshot: greedy in admission order (the
    scheduler's grant order *is* the fairness contract — re-sorting by
    length here would starve long prompts), ``pack_width`` rows per call,
    each call padded to the bucket of its longest member.  Returns
    ``[(bucket, row_indices), ...]``; indices into ``lengths``.  A pack may
    mix prompts whose individual buckets differ — padding them to the
    shared bucket is still bitwise-exact, only compute-wasteful, and the
    waste is bounded by the power-of-two bucket spacing."""
    packs, cur = [], []
    for i in range(len(lengths)):
        cur.append(i)
        if len(cur) == pack_width:
            packs.append(cur)
            cur = []
    if cur:
        packs.append(cur)
    return [
        (bucket_for(max(lengths[i] for i in rows), buckets), rows)
        for rows in packs
    ]


# ---------------------------------------------------------------------------
# jit plumbing (lazy jax)
# ---------------------------------------------------------------------------

def _import_jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


class CountingJit:
    """``jax.jit`` wrapper that counts traces.

    The trace counter is a Python side effect *inside* the traced function,
    so it increments exactly once per (re)trace — the compile-count
    regression tests and the serving bench pin their trace-budget claims on
    it."""

    def __init__(self, fn, **jit_kwargs):
        jax, _ = _import_jax()
        self.traces = 0

        def counted(*args, **kwargs):
            self.traces += 1
            return fn(*args, **kwargs)

        self._fn = jax.jit(counted, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


class PrefillBatcher:
    """Owns the bucketed/packed prefill traces for one engine.

    All packed calls share a fixed row count (``pack_width``): partial packs
    pad with length-0 dummy rows rather than tracing a narrower batch, so
    the trace key varies only in the bucket.  ``warm`` compiles every bucket
    at construction; serving then never traces."""

    def __init__(self, model, *, cache_len: int, pack_width: int, cache_headroom: int = 8):
        gate = getattr(model, "supports_packed_prefill", None)
        if gate is None or not gate(cache_len):
            raise ValueError(
                "this arch cannot take the packed-prefill path (RG-LRU state, "
                "MoE capacity routing, sliding-window rings or patch prefixes "
                "absorb padded positions, or a bucket would leave the attn_xla "
                "dispatch of the per-request reference); DecodeEngine "
                "prefills this model one request at a time"
            )
        jax, jnp = _import_jax()
        self.model = model
        self.cache_len = cache_len
        self.pack_width = pack_width
        self.buckets = prompt_buckets(cache_len)
        self.packed = CountingJit(
            functools.partial(model.prefill_packed, cache_headroom=cache_headroom)
        )
        self.cont = CountingJit(model.prefill_cont)
        # per-leaf batch-axis map (same convention as SlotCache.zeros) + a
        # zero (batch=1) row: the pad filler for partial continuation packs
        # and the warm template
        abs_cache = model.cache_abstract(pack_width, cache_len)
        logical = model.cache_logical(abs_cache)
        self.axes = jax.tree.map(
            lambda l: l.index("batch") if "batch" in l else None,
            logical,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(i, (str, type(None))) for i in x),
        )
        self.axes["pos"] = None
        single = model.cache_abstract(1, cache_len)
        self._zero_row = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), single)
        self._zero_row["pos"] = jnp.zeros((), jnp.int32)

    # -- packing ---------------------------------------------------------------
    def pack_tokens(self, prompts):
        """Right-pad ``prompts`` (<= pack_width of them) into one
        (pack_width, bucket) token array + true lengths; trailing rows are
        dummies (length 0)."""
        import numpy as np

        if len(prompts) > self.pack_width:
            raise ValueError(f"{len(prompts)} prompts exceed pack_width={self.pack_width}")
        b = bucket_for(max((len(p) for p in prompts), default=1), self.buckets)
        toks = np.zeros((self.pack_width, b), np.int32)
        lens = np.zeros((self.pack_width,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = np.asarray(p, np.int32)
            lens[i] = len(p)
        return toks, lens

    def prefill(self, params, prompts):
        """One packed prefill call: (per-row logits, cache with per-row pos)."""
        toks, lens = self.pack_tokens(prompts)
        return self.packed(params, toks, lens)

    def continue_rows(self, params, rows, suffixes):
        """One continuation call: extend each (batch=1) seeded cache in
        ``rows`` by its suffix.  Rows must share the ``SlotCache.fit_single``
        shape (stored prefix caches do, by the store's deposit contract)."""
        import numpy as np

        if len(rows) != len(suffixes) or len(rows) > self.pack_width:
            raise ValueError("rows/suffixes mismatch or pack_width exceeded")
        b = bucket_for(max((len(s) for s in suffixes), default=1), self.buckets)
        toks = np.zeros((self.pack_width, b), np.int32)
        lens = np.zeros((self.pack_width,), np.int32)
        for i, s in enumerate(suffixes):
            toks[i, : len(s)] = np.asarray(s, np.int32)
            lens[i] = len(s)
        cache = self._stack(list(rows) + [self._zero_row] * (self.pack_width - len(rows)))
        return self.cont(params, cache, toks, lens)

    # -- row plumbing ----------------------------------------------------------
    def _stack(self, rows):
        """Stack ``pack_width`` (batch=1) caches into one batched cache."""
        jax, jnp = _import_jax()
        out = {}
        for key in rows[0]:
            if key == "pos":
                out["pos"] = jnp.stack(
                    [jnp.asarray(r["pos"], jnp.int32).reshape(()) for r in rows]
                )
                continue
            out[key] = jax.tree.map(
                lambda ax, *leaves: jnp.concatenate(
                    [jnp.asarray(l) for l in leaves], axis=ax
                ),
                self.axes[key],
                *[r[key] for r in rows],
            )
        return out

    def extract_row(self, cache, row: int):
        """Lane ``row`` of a packed cache as a standalone (batch=1) cache
        with the scalar ``pos`` the per-request ``prefill`` emits — what the
        prefix-KV store deposits and ``SlotCache.fit_single`` refits."""
        jax, _ = _import_jax()

        def take(ax, src):
            if ax is None:
                return src
            return jax.lax.dynamic_slice_in_dim(src, row, 1, axis=ax)

        out = {}
        for key in cache:
            if key == "pos":
                continue
            out[key] = jax.tree.map(take, self.axes[key], cache[key])
        out["pos"] = cache["pos"][row]
        return out

    # -- AOT warm ---------------------------------------------------------------
    def warm(self, params, *, cont: bool = False):
        """Compile every bucket trace ahead of time (and the continuation
        traces too when a prefix-KV store will feed them).  Construction-time
        cost; the serving loop then runs trace-free — the whole point of the
        bucketing."""
        import numpy as np

        cache = self._stack([self._zero_row] * self.pack_width) if cont else None
        for b in self.buckets:
            toks = np.zeros((self.pack_width, b), np.int32)
            lens = np.zeros((self.pack_width,), np.int32)
            self.packed(params, toks, lens)
            if cont:
                self.cont(params, cache, toks, lens)
