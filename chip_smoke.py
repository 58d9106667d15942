#!/usr/bin/env python3
"""Smoke run of the live paged serving path on one TPU chip.

Drives ``serving.engine.Engine`` through the entry points a deployment
uses (a ``Registry``, ``Engine.submit``, ``Engine.step``) with
``agent-1b`` at full width (16 layers, d_model 2048, 16 heads, 8 KV
heads, d_ff 5632, vocab 32000, bf16), random weights from ``--seed``,
the paged KV pool, the Pallas paged-decode kernel and a prefix cache.

    python chip_smoke.py [--seed 0]

Phases, in one process:

1. serialized paged prefill (``prefill_chunk`` 512) and decode steps
   through the kernel, with a shared 1024-token prefix admitted from
   the prefix cache;
2. one decode step's logits through the kernel against the jnp-gather
   path (``use_pallas=False``) over the same params and pool;
3. ``mixed`` switched on through the registry: MIXED steps co-run
   prefill chunks with the live decode batch, and a mid-run
   ``prefill_chunk`` retune to 256 must not retrace the mixed step.

Every check that fails exits non-zero.  There is no CPU fallback: off
TPU the script exits 2 before it builds anything.  Earlier lines report
what ran; the last line of stdout is one JSON object naming the device.
This is a bring-up check, not a benchmark: its times include
compilation.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import Registry  # noqa: E402
from repro.core.types import Request, RequestState  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.prefix_cache import PrefixCache  # noqa: E402
from repro.serving.scheduler import SchedulerConfig, StepKind  # noqa: E402

MODEL = "agent-1b"
ENGINE = "smoke-0"
SLOTS = 8
MAX_CONTEXT = 4096
PAGE = 128
# HBM kept free of the pool reckoning: the other step executables, the
# runtime's own buffers and fragmentation
HBM_MARGIN = 3 << 30

N_REQUESTS = 8
SHARED_PREFIX = 1024            # tokens shared by the even-numbered requests
PROMPT_LEN = (512, 2048)        # inclusive range of prompt lengths
NEW_TOKENS = 32
PREFILL_CHUNK = 512
RETUNED_CHUNK = 256
# kernel vs jnp logits, both over bf16 params and pool: the two paths
# round the attention output differently (kernel: f32 softmax and
# accumulation, one cast at the end; jnp: bf16 probabilities into the
# value einsum).  At agent-1b width that is about 2**-7 of the largest
# logit after one layer, and it grows slowly with depth; each path is
# as far from a float32 forward as from the other.  A kernel that
# drops or misplaces a page moves the logits by their own scale.
# Bound: 2**-4 of the largest reference logit.
LOGIT_TOL = 2.0 ** -4


def smoke_model():
    """agent-1b at full width on the paged + Pallas path."""
    return get_config(MODEL).replace(use_pallas=True)


def scheduler_config(num_pages: int) -> SchedulerConfig:
    return SchedulerConfig(max_slots=SLOTS, max_context=MAX_CONTEXT,
                           page_size=PAGE, num_pages=num_pages,
                           prefill_chunk=PREFILL_CHUNK)


def _layer_kv_bytes(cfg, pages: int) -> int:
    """One layer's K and V pools: ``(pages + 1)`` pages (one write sink)."""
    return (2 * (pages + 1) * PAGE * cfg.n_kv_heads * cfg.d_head
            * jnp.dtype(cfg.dtype).itemsize)


def pool_footprint(cfg, weight_bytes: int, pages: int) -> int:
    """HBM a paged decode step needs at ``pages``: the weights, the pool
    twice (the donated pool, and the fresh copy the layer scan builds as
    its stacked output), and two one-layer K+V buffers (the layer's
    slice and its written update; the kernel reads the pool as
    stored)."""
    return weight_bytes + (2 * cfg.n_layers + 2) * _layer_kv_bytes(cfg, pages)


def pool_pages(cfg, weight_bytes: int, hbm_bytes: int) -> int:
    """Largest pool whose ``pool_footprint`` fits ``hbm_bytes`` less
    ``HBM_MARGIN``."""
    per_page = _layer_kv_bytes(cfg, 0) * (2 * cfg.n_layers + 2)
    return (hbm_bytes - HBM_MARGIN - weight_bytes) // per_page - 1


def make_requests(vocab: int, seed: int) -> list[Request]:
    """``N_REQUESTS`` prompts drawn from ``seed``; the even-numbered ones
    start with one shared ``SHARED_PREFIX``-token prefix."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    lo, hi = PROMPT_LEN
    reqs = []
    for i in range(N_REQUESTS):
        if i % 2 == 0:
            n = int(rng.integers(SHARED_PREFIX + PAGE, hi + 1))
            prompt = np.concatenate(
                [prefix, rng.integers(0, vocab, n - SHARED_PREFIX)])
        else:
            prompt = rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
        reqs.append(Request(prompt_len=len(prompt),
                            max_new_tokens=NEW_TOKENS,
                            prompt_tokens=prompt.astype(np.int32)))
    return reqs


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def _compile_seconds() -> Counter:
    """Backend compile seconds per jitted function, as JAX reports them."""
    secs: Counter = Counter()

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            secs[kw.get("fun_name", "?")] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return secs


def _decode_logits_fn(cfg):
    @jax.jit
    def logits(params, tokens, cache, tables):
        return models.decode_step(params, cfg, tokens, cache, tables)[0]
    return logits


def logit_check(eng: Engine, live: list[Request]) -> float:
    """Max |kernel - jnp| over the live rows of one decode step, relative
    to the largest reference logit.  Neither call donates: both read the
    engine's pool as it stands."""
    tokens = jnp.asarray(eng._last_token[:, None])
    tables = jnp.asarray(eng._block_table_rows(live))
    rows = np.asarray([r.slot for r in live])
    got = _decode_logits_fn(eng.cfg)(eng.params, tokens, eng.cache, tables)
    ref = _decode_logits_fn(eng.cfg.replace(use_pallas=False))(
        eng.params, tokens, eng.cache, tables)
    got = np.asarray(got, np.float32)[rows]
    ref = np.asarray(ref, np.float32)[rows]
    check(got.shape == ref.shape == (len(rows), eng.cfg.vocab)
          and bool(np.isfinite(got).all() and np.isfinite(ref).all()),
          "decode logits are finite, one vocab row per live sequence")
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    print(f"logits kernel vs jnp: rows={len(rows)} max_rel_err={err} "
          f"argmax_agreement={agree}", flush=True)
    return err


def run(seed: int):
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    compile_secs = _compile_seconds()
    hbm = int(dev.memory_stats()["bytes_limit"])

    cfg = smoke_model()
    t0 = time.perf_counter()
    params = jax.block_until_ready(models.init(cfg, jax.random.key(seed)))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    pages = pool_pages(cfg, weight_bytes, hbm)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} weight_bytes={weight_bytes}",
          flush=True)
    print(f"pool pages: {pages} x {PAGE} tokens "
          f"(kv_bytes={_layer_kv_bytes(cfg, pages) * cfg.n_layers} "
          f"reckoned_step_bytes={pool_footprint(cfg, weight_bytes, pages)} "
          f"hbm_bytes_limit={hbm} margin={HBM_MARGIN})", flush=True)

    eng = Engine(cfg, params, scheduler_config(pages), name=ENGINE,
                 seed=seed)
    check(eng.cache_layout == "paged", "engine runs the paged layout")
    cache = eng.attach_cache(PrefixCache(eng.scheduler.alloc,
                                         name=f"{ENGINE}.cache",
                                         block_tokens=PAGE))
    registry = Registry()
    registry.register(eng)
    reqs = make_requests(cfg.vocab, seed)
    kinds: Counter = Counter()

    def step():
        kinds[eng.step().value] += 1

    def prefilling(rs):
        return eng.busy and any(r.prefilled < r.prompt_len for r in rs)

    t_serve = time.perf_counter()
    # phase 1: serialized prefill, then decode through the kernel.  The
    # first request prefills alone so its prefix blocks are committed to
    # the cache before the next sharer is admitted.
    eng.submit(reqs[0])
    while prefilling(reqs[:1]):
        step()
    for r in reqs[1:4]:
        eng.submit(r)
    while prefilling(reqs[:4]):
        step()
    step()
    live = [r for r in reqs[:4] if r.state == RequestState.RUNNING]
    check(kinds[StepKind.DECODE.value] > 0 and len(live) == 4,
          "decode step ran with 4 live sequences")
    err = logit_check(eng, live)
    check(err <= LOGIT_TOL,
          f"kernel logits match jnp within {LOGIT_TOL} (got {err})")
    lowered = eng._decode_paged_fn.lower(
        eng.params, jnp.asarray(eng._last_token[:, None]), eng.cache,
        jnp.asarray(eng._block_table_rows(live)), jax.random.key(0),
        jnp.float32(0.0))
    compiled = lowered.compile()
    print(f"decode step memory_analysis: {compiled.memory_analysis()}",
          flush=True)
    check("tpu_custom_call" in compiled.as_text(),
          "compiled paged decode step contains tpu_custom_call")

    # phase 2: mixed batching switched on through the control plane;
    # retuning the chunk mid-run must replay the one compiled step
    registry.set(ENGINE, "mixed", True)
    for r in reqs[4:]:
        eng.submit(r)
    while eng.busy and kinds[StepKind.MIXED.value] < 2:
        step()
    registry.set(ENGINE, "prefill_chunk", RETUNED_CHUNK)
    while eng.busy:
        step()
    wall = time.perf_counter() - t_serve

    print("compile seconds: " + " ".join(
        f"{k}={v:.3f}" for k, v in sorted(compile_secs.items())), flush=True)
    print("steps: " + " ".join(f"{k}={v}" for k, v in sorted(kinds.items())),
          flush=True)
    tokens = sum(len(r.output_tokens) for r in reqs)
    print(f"tokens generated: {tokens}", flush=True)
    print(f"prefix cache: hit_tokens={cache.hit_tokens} "
          f"miss_tokens={cache.miss_tokens}", flush=True)
    print(f"wall seconds: serve={wall} total={time.perf_counter() - t0}",
          flush=True)
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}",
          flush=True)
    check(all(r.state == RequestState.FINISHED
              and len(r.output_tokens) == NEW_TOKENS
              and all(0 <= t < cfg.vocab for t in r.output_tokens)
              for r in reqs),
          f"all {N_REQUESTS} requests finished with {NEW_TOKENS} in-vocab "
          "tokens")
    check(all(kinds[k.value] > 0 for k in
              (StepKind.PREFILL, StepKind.DECODE, StepKind.MIXED)),
          "prefill, decode and mixed steps all ran")
    check(cache.hit_tokens >= (N_REQUESTS // 2 - 1) * SHARED_PREFIX,
          "shared prefix admitted from the prefix cache")
    check(eng.mixed_step_traces == 1,
          f"mixed_step_traces == 1 after the prefill_chunk retune "
          f"(got {eng.mixed_step_traces})")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the weights and the requests")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 2
    try:
        dev = run(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
