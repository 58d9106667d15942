"""The comparison that decides ``correct``.

After the window closes and the program's state is freed, a sample of
the requests the timed path finished (drawn from the seed, with the
longest request and the one with the most output in it, and at least
``MIN_TOKENS`` served tokens where there are that many) goes through the
configuration's plain reference once per request, over its prompt and
the tokens it was served.  At every served position the number compared
is the gap by which the served token's reference logit lies below the
reference's largest logit there; the cell's reading is the widest gap.
Tokens are greedy, so a sound program serves the reference's best token
up to rounding, and the gap stays near zero.

The limit is the configuration's ``correct.max_logit_gap``; PERF.md
gives the readings it was set from (sound runs over a dozen seeds, and
the reference computed in fp8 as the control).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

from chipbench import weights

MIN_TOKENS = 384
MAX_REQUESTS = 8
CONTROL = "fp8"       # the reference's precision below bfloat16


def sample(done: list[tuple[np.ndarray, list[int]]], seed: int) -> list[int]:
    """Indices into ``done`` (prompt, served tokens) to compare."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][0]) + len(done[i][1]))
    most = max(range(len(done)), key=lambda i: len(done[i][1]))
    picked = list(dict.fromkeys([longest, most]))
    for i in rng.permutation(len(done)):
        if (len(picked) >= MAX_REQUESTS
                or sum(len(done[j][1]) for j in picked) >= MIN_TOKENS):
            break
        if int(i) not in picked:
            picked.append(int(i))
    return picked


def reference(config: dict):
    """The configuration's plain reference module, by name."""
    path = (Path(__file__).resolve().parent / "references"
            / f"{config['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{config['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gaps(m: dict, ref, w, prompt: np.ndarray, served: list[int],
         control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Per served position: the served token's gap below the reference's
    best logit, and, with ``control``, the gap of the token that the
    control's logits put first."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    want = np.asarray(ref.logits_at(w, m, seq, pos), np.float64)
    best = want.max(-1)
    got = want[np.arange(len(served)), np.asarray(served)]
    ctrl = None
    if control:
        first = np.asarray(ref.logits_at(w, m, seq, pos, CONTROL)).argmax(-1)
        ctrl = best - want[np.arange(len(served)), first]
    return best - got, ctrl


def check(config: dict, m: dict, seed: int,
          done: list[tuple[np.ndarray, list[int]]],
          control: bool = False) -> dict:
    """Reference readings over the sampled requests.  ``m`` is the
    model's sizes as run.  Returns the widest gap, what it was read
    over, and the control's widest gap when asked for."""
    picked = sample(done, seed)
    out = {"requests": len(picked),
           "tokens": int(sum(len(done[i][1]) for i in picked)),
           "in_vocab": all(0 <= t < m["vocab"]
                           for i in picked for t in done[i][1])}
    if not picked or not out["in_vocab"]:
        return out
    ref = reference(config)
    w = weights.make(m, seed)
    worst, worst_ctrl = 0.0, 0.0
    for i in picked:
        g, c = gaps(m, ref, w, done[i][0], done[i][1], control)
        worst = max(worst, float(g.max()))
        if c is not None:
            worst_ctrl = max(worst_ctrl, float(c.max()))
    out["max_logit_gap"] = worst
    if control:
        out["control_max_logit_gap"] = worst_ctrl
    return out


def verdict(reading: dict, limit: float) -> tuple[bool, dict]:
    """(correct, the numbers compared beside their limits)."""
    value = reading.get("max_logit_gap")
    compared = {"max_logit_gap": {"value": value, "limit": limit,
                                  "must_be": "at_most"},
                "sampled_tokens": {"value": reading["tokens"], "limit": 1,
                                   "must_be": "at_least"}}
    ok = (value is not None and reading["in_vocab"]
          and reading["tokens"] >= 1 and value <= limit)
    return ok, compared
