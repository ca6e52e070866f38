"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model at the configuration's sizes with params drawn from
the seed, warms every shape its traffic uses, offers the traffic for
``--seconds`` on the host clock, checks what was served against the float32
reference, and prints one JSON line last on standard output.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics.
It fails, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / ".jax_cache"
SRC = ROOT / "src"


def process_start() -> float:
    """Wall-clock time at which this process started (Linux /proc), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = process_start()


class CompileEvents:
    """Counts what JAX reports about compiling: backend compiles and their
    seconds, and persistent-cache hits and misses."""

    def __init__(self, monitoring):
        self.compile_s = 0.0
        self.compiles = 0
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
            self.compiles += 1
            self.compile_s += duration

    @property
    def loaded(self) -> int:
        """Programs compiled or read back from the persistent cache."""
        return self.compiles + self.hits

    def line(self) -> str:
        return (f"backend_compiles={self.compiles} backend_compile_s={self.compile_s:.1f} "
                f"persistent_cache_hits={self.hits} misses={self.misses}")


class RunContext:
    """What a metric reader reads: the cell and its schedule, the host
    records of the window (requests, calls, lateness, ticks), the program's
    own counters at the window's start and end, the peak memory, the
    programs loaded in the window and, in a traced run, the reduced device
    trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def counter(self, name: str):
        """How far one of the program's numeric counters moved in the window."""
        return self.counters_end[name] - self.counters_start[name]

    def in_window(self, t) -> bool:
        return t is not None and self.t0 <= t <= self.t1

    def due_in_window(self):
        return [r for r in self.recs if self.t0 <= r.due < self.t1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _log(msg: str):
    print(msg, flush=True)


def run_cell(args, *, on_chip: bool = True, root: Path = ROOT, cell=None,
             control: bool = False) -> dict | None:
    """One run of a cell -> the result object, or None where the chips the
    cell asks for are missing.  ``on_chip=False`` (tests only) skips the
    look for a TPU, the compile cache and the peak table and runs the rest
    where JAX is.  ``cell`` replaces the manifest's cell (the knee sweep
    changes its rate).  ``control`` also reads the float8 control on the
    served tokens (``check["control_gap"]``, judged by ``check.passes``); the
    benchmark's runs do not."""
    sys.path[:0] = [p for p in (str(SRC), str(ROOT)) if p not in sys.path]
    from bench import spec

    cell = cell or spec.cell(args.workload, root)
    if on_chip:
        # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    import jax
    from jax import monitoring

    events = CompileEvents(monitoring)
    devices = jax.devices()
    dev = devices[0]
    _log(f"[device] platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if on_chip and dev.platform != "tpu":
        print(f"bench: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return None
    from bench import check, peaks, serve, work

    peak = peaks.peak_for(dev.device_kind) if on_chip else None
    cfg = cell.config
    prog, ref = cell.module("programs"), cell.module("references")
    cache_len = cfg["serving"]["cache_len"]
    marks = {"start": T_START, "jax": time.time()}

    model = prog.build(cfg)
    params = jax.block_until_ready(prog.init_params(model, args.seed, dev))
    marks["params"] = time.time()
    engine = prog.engine(model, params, cfg)
    marks["engine"] = time.time()
    schedule = cell.schedule(args.seed, args.seconds)
    loop = cell.loop(schedule.loop)
    serve.warm(engine, schedule.prompt_range, cfg["vocab_size"])
    marks["warm"] = time.time()
    driver = serve.Driver(engine, annotate=bool(args.trace))
    counters_start = prog.counters(engine)
    loaded0 = events.loaded
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    setup_s = time.time() - T_START
    with driver.span("window"):
        t0, t1 = driver.run(loop, schedule, args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    loaded_in_window = events.loaded - loaded0
    counters_end = prog.counters(engine)
    mem_peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices[: cell.chips]) \
        if on_chip else 0
    split = " ".join(f"{b}_s={marks[b] - marks[a]:.2f}" for a, b in
                     zip(["start", "jax", "params", "engine"], ["jax", "params", "engine", "warm"]))
    _log(f"[setup] setup_s={setup_s:.3f} {split} {events.line()}")
    late = sorted(driver.lateness)
    _log(f"[load] lateness_p99_ms={1e3 * late[int(0.99 * (len(late) - 1))] if late else 0:.2f} "
         f"submitted={len(driver.recs)} ticks={driver.n_ticks} "
         f"programs_loaded_in_window={loaded_in_window} "
         f"memory_peak_bytes={mem_peak} counters={json.dumps(counters_end, default=str)}")

    reduced = None
    if trace_dir is not None:
        from bench import trace

        t_parse = time.time()
        reduced = trace.reduce(trace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        _log(f"[trace] parse_s={time.time() - t_parse:.1f} pairs={len(reduced.pairs)} "
             f"calls={len(driver.calls)} shift_ns={reduced.shift}")

    ctx = RunContext(cell=cell, schedule=schedule, dims=work.Dims.from_config(cfg), peak=peak,
                     t0=t0, t1=t1, seconds=t1 - t0, setup_s=setup_s, recs=driver.recs,
                     calls=driver.calls, lateness=driver.lateness, n_ticks=driver.n_ticks,
                     counters_start=counters_start, counters_end=counters_end,
                     memory_peak_bytes=mem_peak, loaded_in_window=loaded_in_window,
                     trace=reduced)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    finished = [(r.prompt, list(r.req.out)) for r in driver.recs if r.finish is not None]
    attempted = len(driver.recs)
    failed = sum(r.failed for r in driver.recs)
    del driver, engine, ctx
    gc.collect()
    t_check = time.time()
    lim = cell.limits
    seqs = check.sample(finished, lim["check_requests"], args.seed)
    got = check.gaps(ref, params, cfg, seqs, width=cache_len,
                     out_max=schedule.out_max, block=lim["block_rows"], control=control)
    gap = got["max_logit_gap"]
    correct = bool(seqs) and check.passes(gap, lim)
    _log(f"[check] requests={len(seqs)} of {len(finished)} finished, "
         f"tokens={got['tokens']} check_s={time.time() - t_check:.1f}")

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": int(mem_peak)}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_ns * 1e-9
        result["device"]["window_s"] = (reduced.window[1] - reduced.window[0]) * 1e-9
        result["breakdown"] = {"device_ops": [list(x) for x in reduced.device_ops],
                               "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    result["check"] = {"max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
                       "served_tokens": {"value": got["tokens"], "limit": 1}}
    if control:
        result["check"]["control_gap"] = {"value": got["control_gap"],
                                          "limit": lim["max_logit_gap"]}
    return result


def main(argv=None) -> int:
    result = run_cell(parse(argv))
    if result is None:
        return 1
    sys.stdout.flush()
    for name, c in result["check"].items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
