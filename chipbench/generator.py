"""One traffic generator for every mix; a mix is a JSON file of parameters.

Every random size of a mix (prompt and output lengths, gaps between
arrivals, the session a turn belongs to, the sessions' starting
histories) is drawn as a stratified quantile: each consecutive block of
``BLOCK`` requests takes the block's ``BLOCK`` midpoint quantiles of the
distribution, in an order drawn from the mix's ``order_seed`` and not
from the run's seed.  So every seed serves the same sizes at the same
drawn times; the seed draws the token ids (and the harness the weights).
An open-loop window holds some tens of requests, and with the order
drawn from the seed the work that fell inside it, and so its tokens/s
and its TTFT tail, moved by a fifth from seed to seed while two runs of
one seed agreed (PERF.md).  A claim measured on one order is checked on
another by a mix file that differs in ``order_seed`` alone.

Mix keys:

* ``order_seed``: the seed of the order of sizes and arrivals.
* ``arrival``: ``{"process": "poisson", "rate": r}``,
  ``{"process": "gamma", "rate": r, "cv": c}`` (open loop, requests/s:
  r x seconds requests, whose gaps are the stratified quantiles of one
  block as long as the window, scaled so that their mean is exactly 1/r;
  the first is due as the window opens), or
  ``{"process": "backlog", "requests": n}`` (all queued at t=0).
* ``prompt`` and ``output``: ``{"dist": "loguniform"|"uniform", "lo",
  "hi"}`` in tokens.  With ``sessions``, ``prompt`` is the new tool
  result of a turn.
* ``shared_prefix``: tokens of one system prompt every request starts
  with (0 for none).
* ``sessions``: ``{"count": k, "zipf_s": s}``: closed-loop agent
  sessions (``Sessions``).  Each turn belongs to a session drawn by
  Zipf(s).  An agent sends its next turn only once its last answer has
  come back, so a turn is due at the later of its drawn time and the
  moment its session's previous turn finished, and its prompt is made
  then: the system prompt, the session's conversation so far (its
  earlier prompts and the answers actually served) and the turn's new
  tool result.  A session restarts empty when prompt and output would
  pass the engine's ``max_context``.  Sessions start with stratified
  histories of up to ``max_context - shared_prefix - prompt.hi -
  output.hi`` tokens, which set-up prefills into the prefix cache.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


BLOCK = 32


@dataclass
class Req:
    due: float                 # drawn: seconds after the window opens
    prompt: np.ndarray         # int32 token ids; a session's tool result
    max_new: int
    session: int = -1


class Sessions:
    """The closed loop of agent sessions: which may send a turn, when it
    is due, and its prompt.  The harness calls ``prompt`` as it submits a
    turn and ``answered`` once the turn's last token came back."""

    def __init__(self, shared: np.ndarray, history: list[np.ndarray],
                 max_context: int):
        self.shared = shared
        self.conv = list(history)          # each session's turns so far
        self.max_context = max_context
        self.free_at = [0.0] * len(history)
        self.busy = [False] * len(history)

    def ready(self, s: int) -> bool:
        return not self.busy[s]

    def due(self, req: Req) -> float:
        return max(req.due, self.free_at[req.session])

    def prompt(self, req: Req) -> np.ndarray:
        s = req.session
        if (len(self.shared) + len(self.conv[s]) + len(req.prompt)
                + req.max_new > self.max_context):
            self.conv[s] = self.conv[s][:0]
        self.busy[s] = True
        return np.concatenate([self.shared, self.conv[s], req.prompt])

    def answered(self, s: int, prompt: np.ndarray, served: list[int],
                 t: float) -> None:
        self.conv[s] = np.concatenate(
            [prompt[len(self.shared):], np.asarray(served, np.int32)])
        self.busy[s] = False
        self.free_at[s] = t


@dataclass
class Traffic:
    requests: list[Req]
    setup: list[np.ndarray] = field(default_factory=list)   # prompts to
    #                            prefill (one output token) before the window
    sessions: Sessions | None = None


def _quantiles(rng, n: int, block: int) -> np.ndarray:
    """n quantile levels; each block is a permutation of its midpoints."""
    mids = (np.arange(block) + 0.5) / block
    out = np.concatenate([rng.permutation(mids)
                          for _ in range(-(-n // block))])
    return out[:n]


def _lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if spec["dist"] == "loguniform":
        x = lo * (hi / lo) ** u
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(arrival: dict, u: np.ndarray, n: int) -> np.ndarray:
    from scipy import stats
    if arrival["process"] == "poisson":
        q = stats.expon.ppf(u)
    elif arrival["process"] == "gamma":
        shape = 1.0 / float(arrival["cv"]) ** 2
        q = stats.gamma.ppf(u, a=shape)
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    # every full block holds the same quantile set: scale by that set's
    # mean so the offered rate is exactly the mix's rate
    mids = (np.arange(n) + 0.5) / n
    ref = stats.expon.ppf(mids) if arrival["process"] == "poisson" \
        else stats.gamma.ppf(mids, a=shape)
    return q / ref.mean() / float(arrival["rate"])


def scaled(mix: dict, f: float) -> dict:
    """The mix with every length multiplied by ``f`` (CPU rehearsal)."""
    out = dict(mix)
    for k in ("prompt", "output"):
        out[k] = dict(mix[k], lo=max(1, int(mix[k]["lo"] * f)),
                      hi=max(1, int(mix[k]["hi"] * f)))
    out["shared_prefix"] = int(mix.get("shared_prefix", 0) * f)
    return out


def generate(mix: dict, vocab: int, max_context: int, seconds: float,
             seed: int) -> Traffic:
    rng = np.random.default_rng(int(mix["order_seed"]))   # sizes, arrivals
    ids = np.random.default_rng([seed, 0])                # token ids
    arrival = mix["arrival"]
    if arrival["process"] == "backlog":
        n = int(arrival["requests"])
        due = np.zeros(n)
    else:
        # rate x seconds requests, whose gaps are one stratified block:
        # every seed offers the same count, due from 0 to within the window
        n = max(1, int(round(float(arrival["rate"]) * seconds)))
        gaps = _gaps(arrival, _quantiles(rng, n, n), n)
        due = np.cumsum(gaps) - gaps[0]
    prompt_len = _lengths(mix["prompt"], _quantiles(rng, n, BLOCK))
    out_len = _lengths(mix["output"], _quantiles(rng, n, BLOCK))

    def toks(k: int) -> np.ndarray:
        return ids.integers(0, vocab, int(k), dtype=np.int32)

    shared = toks(mix.get("shared_prefix", 0))
    sessions = mix.get("sessions")
    if not sessions:
        reqs = [Req(float(due[i]), np.concatenate([shared, toks(prompt_len[i])]),
                    int(out_len[i])) for i in range(n)]
        return Traffic(reqs, [shared] if len(shared) else [])

    k = int(sessions["count"])
    w = 1.0 / np.arange(1, k + 1) ** float(sessions["zipf_s"])
    cdf = np.cumsum(w) / w.sum()
    who = np.minimum(np.searchsorted(cdf, _quantiles(rng, n, BLOCK)), k - 1)
    room = max_context - len(shared) - mix["prompt"]["hi"] - mix["output"]["hi"]
    start = np.rint(_quantiles(rng, k, k) * max(room, 0)).astype(np.int64)
    hist = [toks(s) for s in start]
    setup = [shared] + [np.concatenate([shared, h]) for h in hist if len(h)]
    reqs = [Req(float(due[i]), toks(prompt_len[i]), int(out_len[i]),
                int(who[i])) for i in range(n)]
    return Traffic([r for r in reqs if r.due < seconds], setup,
                   Sessions(shared, hist, max_context))
