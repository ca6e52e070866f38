"""Readings a cell's limits and rates are set from, on the chip, in one
process (the benchmark's own runs do none of this).

    python3 bench/calibrate.py readings --workload <cell> --seconds <s> --seeds 1 2 3
    python3 bench/calibrate.py sweep --workload <cell> --seconds <s> --seed 1 --rates 1 2 3

``readings`` runs the cell once per seed with the float8 control read on the
same served tokens, and prints the program's and the control's widest logit
gap per seed, each judged by ``check.passes`` against the cell's limit: the
lower reading of the limit is the largest of the first, the upper the
smallest of the second.  ``sweep`` offers an open-loop mix at
each rate in turn, to find the knee the cell's rate is set below.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import check, run, spec  # noqa: E402


def _args(workload, seed, seconds):
    return run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])


def readings(opts) -> int:
    gaps, ctl = [], []
    for seed in opts.seeds:
        r = run.run_cell(_args(opts.workload, seed, opts.seconds), control=True)
        if r is None:
            return 1
        c = r["check"]
        gaps.append(c["max_logit_gap"]["value"])
        ctl.append(c["control_gap"]["value"])
        print(f"[reading] seed={seed} max_logit_gap={gaps[-1]} control_gap={ctl[-1]} "
              f"served_tokens={c['served_tokens']['value']} correct={r['correct']} "
              f"control_correct={check.passes(ctl[-1], spec.cell(opts.workload).limits)} "
              f"metrics={json.dumps(r['metrics'])}", flush=True)
    print(f"[readings] {opts.workload} seeds={len(gaps)} lower={max(gaps)} "
          f"upper={min(ctl)} ratio={min(ctl) / max(max(gaps), 1e-30):.2f}", flush=True)
    return 0


def sweep(opts) -> int:
    base = spec.cell(opts.workload)
    for rate in opts.rates:
        cell = copy.deepcopy(base)
        cell.traffic["rate_per_s"] = rate
        r = run.run_cell(_args(opts.workload, opts.seed, opts.seconds), cell=cell)
        if r is None:
            return 1
        m = {k: round(v["value"], 3) for k, v in r["metrics"].items()}
        print(f"[sweep] rate={rate} attempted={r['attempted']} {json.dumps(m)} "
              f"correct={r['correct']}", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    a = sub.add_parser("readings")
    a.add_argument("--workload", required=True)
    a.add_argument("--seconds", type=float, required=True)
    a.add_argument("--seeds", type=int, nargs="+", required=True)
    b = sub.add_parser("sweep")
    b.add_argument("--workload", required=True)
    b.add_argument("--seconds", type=float, required=True)
    b.add_argument("--seed", type=int, default=1)
    b.add_argument("--rates", type=float, nargs="+", required=True)
    opts = ap.parse_args(argv)
    return readings(opts) if opts.what == "readings" else sweep(opts)


if __name__ == "__main__":
    raise SystemExit(main())
