"""Bring-up check of the serving main path on TPU chips.

    python chip_smoke.py               # one chip: serve granite-3-8b
    python chip_smoke.py --four-chips  # four chips: four router replicas

One chip: granite-3-8b at its published widths, cut to 20 of 40 layers
(``granite_3_8b.one_chip``), with random params from a fixed seed (attention
projections at fan-in scale, see ``fan_in_attention``), served
through ``repro.launch.serve``'s own ``--arrivals`` path: CNA admission, the
bucketed/packed/AOT-warmed batched engine, a 1024-position slot cache.  A
few dozen requests of mixed prompt lengths decode 16 tokens each.  The
logits that emitted some requests' final tokens are then compared with a
float32 prefill of the same params and sequences on the same chip.

Four chips (``--four-chips``, this phase only): ``serve_fleet``'s router over
four engine replicas, replica r with its own params and caches on device r,
against the identical fleet run with every replica on device 0; each
session's tokens must agree exactly.

The script runs in one process and starts none.  It fails, printing no
result, where JAX finds no TPU or the repository's ``src/`` is not beside
it.  Its last line is one JSON object naming the device it ran on.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Relative L2 error of the engine's bf16 logits against the float32
# reference, over the real vocabulary.  bf16 keeps 8 significant bits (unit
# roundoff 2^-9 ~ 0.002); 20 layers of bf16 matmul outputs, residual adds and
# a bf16 KV cache compound that to about 1e-2.  0.05 leaves room for that and
# still fails a path computing in fp8 (2^-4 per rounding), or one that drops
# or mis-positions a layer's contribution.
LOGIT_RTOL = 0.05


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


class CompileEvents:
    """Counts what JAX reports about compiling: backend compile seconds and
    persistent-cache hits and misses (a miss is a compile written back)."""

    def __init__(self, monitoring):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def line(self) -> str:
        return (f"backend_compile_s={self.compile_s:.1f} "
                f"persistent_cache_hits={self.hits} misses={self.misses}")


def fan_in_attention(params, cfg):
    """The same random params with the attention projections at fan-in
    scale.  ``ParamBuilder`` draws a weight at 1/sqrt(shape[-2]), which for
    the head-split ``wq``/``wk``/``wv`` (d, heads, hd) and ``wo`` (heads, hd,
    d) is a head count or head_dim rather than the fan-in.  At granite's
    widths q and k then have std ~11 and ~23, scores std ~250, and softmax is
    a hard argmax: any rounding can flip the winning key, so no bf16 run can
    agree with a float32 one.  Rescaled to 1/sqrt(fan-in) they stay random
    from the same seed and the comparison means something."""
    d, h, kv = cfg.d_model, cfg.n_heads, cfg.n_kv
    mult = {"wq": (h / d) ** 0.5, "wk": (kv / d) ** 0.5, "wv": (kv / d) ** 0.5,
            "wo": h ** -0.5}
    blocks = dict(params["blocks"])
    for name, m in mult.items():
        blocks[name] = (blocks[name] * m).astype(blocks[name].dtype)
    return dict(params, blocks=blocks)


def reference_logits(cfg, params, seqs, width: int):
    """Last-position logits of each sequence from a float32 prefill of the
    same (bf16-stored) params at the highest matmul precision: the model's
    own ``prefill_packed`` with a float32 config, whose residual stream and
    matmuls run in f32 while each layer's weights are read as stored."""
    import functools

    import jax
    import numpy as np

    from repro.models.registry import build_model

    ref = build_model(cfg.replace(dtype="float32", attn_impl="xla"))
    toks = np.zeros((len(seqs), width), np.int32)
    lens = np.zeros((len(seqs),), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s
        lens[i] = len(s)
    fn = jax.jit(functools.partial(ref.prefill_packed, cache_headroom=0))
    with jax.default_matmul_precision("highest"):
        logits, _ = fn(params, toks, lens)
    return np.asarray(logits, np.float32)[:, : cfg.vocab]


def one_chip_phase(jax, serve, events) -> str | None:
    """Serve the one-chip config; returns a failure message or None."""
    import numpy as np

    args = serve.parse_args([
        "--preset", "one_chip", "--arrivals", "0.5", "--requests", "32",
        "--slots", "8", "--cache-len", "1024", "--max-new", "16", "--seed", "0",
    ])
    t0 = time.time()
    cfg, model, params = serve.load_model(args)
    params = jax.block_until_ready(fan_in_attention(params, cfg))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[model] {cfg.name} preset={args.preset} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} param_bytes={param_bytes} "
          f"init_s={time.time() - t0:.1f}", flush=True)

    reqs, arrivals = serve.arrival_workload(args, cfg.vocab)
    t0 = time.time()
    eng = serve.arrivals_engine(args, model, params)
    warm = time.time() - t0
    warm_counts = dict(eng.compile_counts)
    print(f"[warmup] seconds={warm:.1f} compile_counts={warm_counts} "
          f"{events.line()}", flush=True)

    ttft, wall = serve.drive_arrivals(eng, reqs, arrivals)
    tokens = sum(len(r.out) for r in reqs)
    lens = [len(r.prompt) for r in reqs]
    waits = np.array([ttft[r.rid] for r in reqs])
    counts = eng.compile_counts
    print(f"[serve] requests={len(reqs)} prompt_len_min={min(lens)} "
          f"max={max(lens)} tokens={tokens} wall_s={wall:.2f} "
          f"tokens_per_s={tokens / wall:.1f} "
          f"ttft_p50_ms={np.percentile(waits, 50) * 1e3:.0f} "
          f"ttft_p99_ms={np.percentile(waits, 99) * 1e3:.0f} "
          f"compile_counts={counts} {events.line()}", flush=True)
    short = [r.rid for r in reqs if len(r.out) != args.max_new]
    if short:
        return f"requests {short} did not decode {args.max_new} tokens"
    if counts["packed_prefill"] != warm_counts["packed_prefill"] or counts["decode"] != 1:
        return f"traced while serving: {warm_counts} -> {counts}"

    # the logits that emitted the final token of the shortest, the longest
    # and two other requests, against the float32 reference
    order = sorted(reqs, key=lambda r: len(r.prompt))
    check = [order[0], order[len(order) // 3], order[2 * len(order) // 3], order[-1]]
    got = np.stack([np.asarray(r.last_logits, np.float32)[: cfg.vocab] for r in check])
    seqs = [list(r.prompt) + r.out[:-1] for r in check]
    del eng
    t0 = time.time()
    want = reference_logits(cfg, params, seqs, args.cache_len)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    print(f"[check] rids={[r.rid for r in check]} seq_lens={[len(s) for s in seqs]} "
          f"rel_l2={[float(x) for x in rel]} "
          f"max_abs={float(np.max(np.abs(got - want)))} "
          f"ref_std={float(np.std(want))} rtol={LOGIT_RTOL} "
          f"ref_s={time.time() - t0:.1f}", flush=True)
    if not np.all(np.isfinite(got)):
        return "engine logits are not finite"
    if not np.all(rel <= LOGIT_RTOL):
        return f"engine logits differ from the float32 reference: rel_l2={rel}"
    return None


def four_chip_phase(jax, serve, devices) -> str | None:
    """Four router replicas on four devices against the same fleet on one."""
    if len(devices) < 4:
        return f"--four-chips needs 4 devices, found {len(devices)}"
    args = serve.parse_args([
        "--preset", "one_chip", "--replicas", "4", "--requests", "24",
        "--slots", "4", "--cache-len", "64", "--prompt-len", "8",
        "--max-new", "6", "--seed", "0",
    ])
    cfg, model, params = serve.load_model(args)
    outs = {}
    for name, devs in (("one_chip", devices[:1]), ("four_chips", devices[:4])):
        sessions = serve.fleet_sessions(args, cfg.vocab)
        router, replicas = serve.build_fleet(args, model, params, devs)
        for rep in replicas:
            want = {devs[rep.rid % len(devs)]}
            where = [set(x.devices()) for x in jax.tree.leaves(
                (rep.engine.params, rep.engine.slots.cache))]
            if any(w != want for w in where):
                return f"{name}: replica {rep.rid} is not wholly on {want}"
        wall = serve.drive_fleet(router, replicas, sessions)
        outs[name] = {s.sid: s.out for s in sessions}
        served = [rep.engine.scheduler.metrics.admitted for rep in replicas]
        print(f"[fleet {name}] devices={[d.id for d in devs]} "
              f"sessions={len(sessions)} served_per_replica={served} "
              f"ships={router.stats.ships} wall_s={wall:.2f}", flush=True)
        del router, replicas
    differ = [sid for sid in outs["one_chip"] if outs["one_chip"][sid] != outs["four_chips"][sid]]
    print(f"[compare] sessions={len(outs['one_chip'])} differing={differ}", flush=True)
    for d in devices[:4]:
        st = d.memory_stats()
        print(f"[memory] device={d.id} bytes_in_use={st['bytes_in_use']} "
              f"peak_bytes_in_use={st['peak_bytes_in_use']}", flush=True)
    if any(len(o) != args.max_new for o in outs["four_chips"].values()):
        return "a session did not decode all its tokens"
    if differ:
        return f"sessions {differ} decoded other tokens on four chips than on one"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase on four chips")
    opts = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        return _fail(f"{SRC / 'repro'} is missing: run from a checkout of the repository")
    sys.path.insert(0, str(SRC))

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    from jax import monitoring

    events = CompileEvents(monitoring)
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (platform {dev.platform!r})")

    from repro.launch import serve

    if opts.four_chips:
        err = four_chip_phase(jax, serve, devices)
    else:
        err = one_chip_phase(jax, serve, events)
        st = dev.memory_stats()
        print(f"[memory] peak_bytes_in_use={st['peak_bytes_in_use']} "
              f"bytes_limit={st.get('bytes_limit')}", flush=True)
    print(f"[compile] {events.line()} "
          f"cache_hit={'yes' if events.hits else 'no'}", flush=True)
    if err:
        return _fail(err)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
