"""How ``correct`` is decided for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the plain
float32 reference once each: the prompt followed by the served tokens,
teacher-forced.  At every served position the reference's best logit is
compared with its logit for the token the program served there.  The number
compared is the widest such gap, ``max_logit_gap``: 0 where the program
served the reference's own argmax everywhere, and small where rounding
flipped a near tie.  A token altered where it is produced, a cache not
written, a layer skipped or weights held in a lower precision read larger.

The control (``control_gap``) reads the same prompts and tokens with the
reference's weights rounded to float8: at each position the token the
control puts first, and the same gap for it.  ``passes`` decides both:
the program's run is correct, and the control's is not, by the one limit.
"""

from __future__ import annotations

import math

import numpy as np

from bench.schedule import seed_rng


def passes(gap: float, limits: dict) -> bool:
    """Whether a widest logit gap lies within the cell's limit."""
    return math.isfinite(gap) and gap <= limits["max_logit_gap"]


def sample(finished: list, k: int, seed: int) -> list:
    """``k`` finished requests (``(prompt, out)`` pairs) drawn from the
    seed, with the longest (prompt + output) among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (len(finished[i][0]) + len(finished[i][1]), i))
    longest, rest = order[-1], order[:-1]
    rng = seed_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) if rest else []
    return [finished[longest]] + [finished[rest[int(i)]] for i in sorted(pick)]


def pack(seqs: list, width: int, out_max: int, block: int):
    """Teacher-forced inputs for ``seqs``: tokens (n, width) = prompt + served
    tokens but the last, the position that emitted each served token
    (n, out_max), the served token there, and which entries are real.  Rows
    pad to a multiple of ``block`` so every reference call has one shape."""
    n = -(-max(len(seqs), 1) // block) * block
    toks = np.zeros((n, width), np.int32)
    pos = np.zeros((n, out_max), np.int32)
    served = np.zeros((n, out_max), np.int32)
    valid = np.zeros((n, out_max), bool)
    for i, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > width or len(out) > out_max:
            raise ValueError(f"sequence of {len(seq)} tokens, {len(out)} served, "
                             f"exceeds ({width}, {out_max})")
        toks[i, : len(seq)] = seq
        m = len(out)
        pos[i, :m] = len(prompt) - 1 + np.arange(m)
        served[i, :m] = out
        valid[i, :m] = True
    return toks, pos, served, valid


def gaps(reference, params, cfg: dict, seqs: list, *, width: int, out_max: int,
         block: int, control: bool = False) -> dict:
    """-> {"max_logit_gap", "tokens"} (and "control_gap" with ``control``):
    widest gap over every served position of ``seqs``."""
    import jax.numpy as jnp

    toks, pos, served, valid = pack(seqs, width, out_max, block)
    worst, worst_ctl = 0.0, 0.0
    for r in range(0, len(toks), block):
        sl = slice(r, r + block)
        ref = reference.logits_at(params, cfg, toks[sl], pos[sl])
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(served[sl])[..., None], axis=-1)[..., 0]
        gap = np.asarray(best - got)
        worst = max(worst, float(np.max(np.where(valid[sl], gap, 0.0))))
        if control:
            ctl = reference.logits_at(params, cfg, toks[sl], pos[sl], quant="fp8")
            pick = jnp.argmax(ctl, axis=-1)
            cgot = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
            cgap = np.asarray(best - cgot)
            worst_ctl = max(worst_ctl, float(np.max(np.where(valid[sl], cgap, 0.0))))
        del ref
    out = {"max_logit_gap": worst, "tokens": int(valid.sum())}
    if control:
        out["control_gap"] = worst_ctl
    return out
