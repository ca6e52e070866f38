"""The general generator: independent requests with stratified lengths.

Every seed gets the same multiset of sizes and of inter-arrival gaps, in
another order: lengths and gaps are the distribution's quantiles at
(i + 1/2)/n, permuted by the seed, and token ids are drawn from the seed.
Two seeds then offer the same work, and runs of different seeds differ only
as much as the order of that work moves them.

Parameters:

* ``loop``: ``open`` (requests due on a schedule, whatever the server does)
  or ``closed`` (``clients`` callers, each sending its next request when the
  previous one has finished, after ``think_s`` seconds).
* ``rate_per_s`` (open): Poisson arrivals at this mean rate; the schedule
  holds rate x the run's seconds requests, all due within the window, so
  every seed offers the window the same work.
* ``prompt_len``, ``output_len``: length distributions (``schedule.lengths``).
* ``requests`` (closed): how many requests the schedule holds; callers take
  them in order and start over at the end.
"""

from __future__ import annotations

import numpy as np

from bench.schedule import Planned, Schedule, exponential_gaps, lengths, seed_rng


def generate(traffic: dict, seed: int, vocab: int, cache_len: int, seconds: float) -> Schedule:
    """The schedule of one run.  Raises if a request could not decode all
    its tokens within ``cache_len`` positions (the engine retires a request
    once its cache holds ``cache_len - 1`` positions)."""
    loop = traffic["loop"]
    if loop == "open":
        n = max(1, round(float(traffic["rate_per_s"]) * seconds))
    elif loop == "closed":
        n = int(traffic["requests"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    p_spec, o_spec = traffic["prompt_len"], traffic["output_len"]
    if int(p_spec["max"]) + int(o_spec["max"]) > cache_len - 1:
        raise ValueError(
            f"prompt max {p_spec['max']} + output max {o_spec['max']} does not "
            f"fit a {cache_len}-position cache")
    rng = seed_rng(seed)
    prompts = rng.permutation(lengths(p_spec, n))
    outs = rng.permutation(lengths(o_spec, n))
    if loop == "open":
        due = np.cumsum(rng.permutation(exponential_gaps(float(traffic["rate_per_s"]), n)))
    else:
        due = [None] * n
    reqs = [
        Planned(i, None if due[i] is None else float(due[i]),
                rng.integers(0, vocab, int(prompts[i])).astype(np.int32), int(outs[i]))
        for i in range(n)
    ]
    return Schedule(loop, reqs, prompt_range=(int(p_spec["min"]), int(p_spec["max"])),
                    out_max=int(o_spec["max"]), clients=int(traffic.get("clients", 0)),
                    think_s=float(traffic.get("think_s", 0.0)))
