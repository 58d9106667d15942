"""Ring vs paged KV layout on the live Engine decode hot path.

Two questions the paged-pool refactor has to answer with numbers:

1. **Step time** — does routing decode through the shared page pool
   (gather per step, block tables as traced jit inputs) cost anything
   against the slot-contiguous ring buffers, at batch 1 and batched?
2. **Admission cost under prefix fan-out** — N agents forking from one
   shared system prompt.  Ring prefills the full prompt N times; paged
   acquires the shared pages *by id* and prefills only each request's
   private suffix — the admission-time KV copy disappears entirely.

CPU-container honesty: absolute times are interpret-mode/XLA-CPU
numbers, so the headline for (2) is *computed prefill tokens*, which is
hardware-independent, with wall time reported alongside.  The Pallas
kernel itself is benchmarked in bench_kernels; here both layouts run
the jnp paths so the comparison isolates the *layout*, not the kernel.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import Report
from repro import models
from repro.configs import get_config
from repro.core.types import Request, RequestState
from repro.serving.engine import Engine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import SchedulerConfig

PAGE = 16


def _engine(cfg, params, layout, max_slots, cache=False, num_pages=256,
            max_context=256):
    sc = SchedulerConfig(max_slots=max_slots, num_pages=num_pages,
                         max_context=max_context, page_size=PAGE)
    eng = Engine(cfg, params, sc, name=f"bench-{layout}",
                 cache_layout=layout)
    if cache:
        eng.attach_cache(PrefixCache(eng.scheduler.alloc,
                                     name=f"bench-{layout}.cache",
                                     block_tokens=PAGE, reserve_frac=0.8))
    return eng


def _req(prompt, max_new):
    return Request(prompt_len=len(prompt), max_new_tokens=max_new,
                   prompt_tokens=np.asarray(prompt, np.int32))


def _decode_step_time(cfg, params, layout, batch, prompt_len, steps):
    """Mean decode-only step time with ``batch`` co-resident sequences.

    The pool is sized to the workload's residency (as a deployment sizes
    its HBM pool).  An oversized pool is not free on any backend: the
    layer scan builds its stacked cache output as a second full pool
    (on a TPU in temporary HBM, next to the donated input), so a larger
    pool would charge the paged layout for capacity it isn't using."""
    pages = -(-(prompt_len + steps + 8) // PAGE) * batch
    eng = _engine(cfg, params, layout, max_slots=batch, num_pages=pages,
                  max_context=128)
    rng = np.random.default_rng(0)
    reqs = [_req(rng.integers(0, cfg.vocab, prompt_len), steps + 4)
            for _ in range(batch)]
    for r in reqs:
        eng.submit(r)
    while any(r.prefilled < r.prompt_len for r in reqs):
        eng.step()
    eng.step()                      # warm the decode jit (trace + compile)
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    return (time.perf_counter() - t0) / steps


def _fanout_admission(cfg, params, layout, fanout, shared_len, suffix_len):
    """Admit ``fanout`` requests forking from one shared prefix; return
    (computed prefill tokens, admission+prefill wall seconds)."""
    pages = -(-(shared_len + suffix_len + 8) // PAGE) * (fanout + 1)
    eng = _engine(cfg, params, layout, max_slots=fanout,
                  cache=(layout == "paged"), num_pages=pages,
                  max_context=128)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab, shared_len)
    reqs = []
    for i in range(fanout):
        suffix = rng.integers(0, cfg.vocab, suffix_len)
        reqs.append(_req(np.concatenate([shared, suffix]), 2))
    # warm both prefill shapes (full prompt + cached-fork suffix) on a
    # throwaway prefix so the timed sweep measures steps, not jit traces
    warm = rng.integers(0, cfg.vocab, shared_len)
    for _ in range(2):
        w = _req(np.concatenate([warm, rng.integers(0, cfg.vocab,
                                                    suffix_len)]), 2)
        eng.submit(w)
        eng.run_until_idle()
    computed = 0
    t0 = time.perf_counter()
    for r in reqs:                  # sequential arrivals: later requests
        eng.submit(r)               # see the earlier ones' shared pages
        while r.prefilled < r.prompt_len:
            eng.step()
        computed += r.prompt_len - r.meta.get("cached_prompt_tokens", 0)
    wall = time.perf_counter() - t0
    eng.run_until_idle()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return computed, wall


def main(smoke: bool = False) -> Report:
    rep = Report("paged vs ring KV layout (live engine)")
    cfg = get_config("tiny-agent").replace(dtype="float32")
    params = models.init(cfg, jax.random.key(0))

    steps = 10 if smoke else 40
    for batch in ([1] if smoke else [1, 4]):
        times = {}
        for layout in ("ring", "paged"):
            times[layout] = _decode_step_time(cfg, params, layout,
                                              batch=batch, prompt_len=48,
                                              steps=steps)
        rep.add(f"decode_b{batch}",
                ring_ms=round(times["ring"] * 1e3, 3),
                paged_ms=round(times["paged"] * 1e3, 3),
                paged_over_ring=round(times["paged"] / times["ring"], 3))

    shared_len, suffix_len = 96, 16
    for fanout in ([4] if smoke else [2, 4, 8]):
        row = {}
        for layout in ("ring", "paged"):
            toks, wall = _fanout_admission(cfg, params, layout, fanout,
                                           shared_len, suffix_len)
            row[f"{layout}_prefill_tokens"] = toks
            row[f"{layout}_admit_s"] = round(wall, 3)
        full = fanout * (shared_len + suffix_len)
        rep.add(f"fanout_{fanout}", **row,
                token_reduction=round(
                    1.0 - row["paged_prefill_tokens"] / full, 3))
    rep.note(f"shared prefix {shared_len} tok, private suffix "
             f"{suffix_len} tok; paged admits later forks by page id "
             f"(zero KV copies), ring recomputes the full prompt")
    rep.note("CPU-container numbers: token_reduction is the "
             "hardware-independent headline; wall times are XLA-CPU")
    return rep


if __name__ == "__main__":
    print(main().render())
