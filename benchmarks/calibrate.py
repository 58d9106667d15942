"""CostModel calibration harness: fit the roofline to *measured* steps.

    PYTHONPATH=src python -m benchmarks.calibrate [--smoke]
                                                  [--out-dir artifacts/bench]

Two things happen per run:

1. **Correctness** (always, interpret mode): the paged decode-attention
   kernel is checked against the ref.py gather-then-attend oracle through
   a shared-prefix block table with non-page-aligned context lengths, and
   the contiguous decode kernel at a non-block-divisible T (the tail-
   truncation regression).
2. **Measurement + fit** (wherever a JAX backend exists — on the CPU
   container this times XLA-CPU, on TPU the real thing): the jitted
   ``models.prefill`` / ``models.decode_step`` functions — the exact
   executables serving/engine.py dispatches — are timed across a
   (batch × context × model-config) grid; sim/calibration.py least-
   squares-fits ``flops_scale`` / ``bytes_scale`` / ``step_overhead``
   and the result is persisted as ``CALIB_<model>.json`` for
   ``CostModel.from_calibration``.

Decode cost depends on the cache's ``max_context`` (the ring is a fixed
shape: every step reads/masks the whole ring), so the decode grid varies
``init_cache``'s max_context — that IS the resident-context axis.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import Report
from repro import models
from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.sim.calibration import (CalibrationPoint, calibrate,
                                   save_calibration)
from repro.sim.costmodel import CostModel

TINY = ModelConfig(name="calib-tiny", family="dense", n_layers=2,
                   d_model=128, n_heads=4, n_kv_heads=2, d_ff=256, vocab=512)
SMALL = ModelConfig(name="calib-small", family="dense", n_layers=4,
                    d_model=256, n_heads=8, n_kv_heads=4, d_ff=512,
                    vocab=1024)

PREFILL_LENS = (64, 128, 256, 512)
DECODE_GRID = ((1, 128), (1, 512), (2, 256), (4, 512), (8, 1024))
PREFILL_LENS_SMOKE = (64, 128)
DECODE_GRID_SMOKE = ((1, 128), (2, 256), (4, 256))


def _time_step(fn, *args, reps: int = 5) -> float:
    out = fn(*args)                      # compile + warm
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# Interpret-mode correctness (runs everywhere, no accelerator needed)
# ---------------------------------------------------------------------------
def kernel_correctness(rep: Report) -> None:
    # paged decode-attention through a shared-prefix block table with
    # non-page-aligned context lengths — the allocator-shaped case
    page, hkv, g, dh = 16, 2, 2, 64
    b, per_seq = 3, 6
    h = hkv * g
    n = 2 + b * (per_seq - 2)            # 2 shared + private pages
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n, hkv, dh, page), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n, hkv, dh, page), jnp.float32)
    rows, nxt = [], 2
    for _ in range(b):                   # same physical prefix ids per row
        rows.append([0, 1] + list(range(nxt, nxt + per_seq - 2)))
        nxt += per_seq - 2
    bt = jnp.asarray(rows, jnp.int32)
    ctx = jnp.asarray([page * per_seq, page * per_seq - 5, 2 * page + 3],
                      jnp.int32)
    out = ops.paged_decode_attention(q, k_pages, v_pages, bt, ctx,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q.reshape(b, hkv, g, dh),
                                          k_pages, v_pages, bt, ctx)
    err = float(jnp.abs(out.reshape(b, hkv, g, dh) - want).max())
    rep.add("calibrate.correctness.paged_decode_attention",
            max_err=f"{err:.2e}", ok=err < 1e-4)

    # contiguous decode kernel at non-block-divisible T (tail regression)
    t = 200
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (2, 1, h, dh), jnp.float32)
    ck = jax.random.normal(ks[1], (2, t, hkv, dh), jnp.float32)
    cv = jax.random.normal(ks[2], (2, t, hkv, dh), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    qp = jnp.full((2,), t - 1)
    out = ops.decode_attention(q, ck, cv, kpos, qp, interpret=True)
    want = ref.decode_attention_ref(q.reshape(2, hkv, g, dh),
                                    jnp.moveaxis(ck, 2, 1),
                                    jnp.moveaxis(cv, 2, 1), kpos, qp[:, None])
    err = float(jnp.abs(out.reshape(2, hkv, g, dh) - want).max())
    rep.add("calibrate.correctness.decode_attention_tail",
            max_err=f"{err:.2e}", ok=err < 1e-4, t=t)


# ---------------------------------------------------------------------------
# Measurement grid
# ---------------------------------------------------------------------------
def measure_points(cfg: ModelConfig, prefill_lens, decode_grid,
                   reps: int = 5) -> list[CalibrationPoint]:
    params = models.init(cfg, jax.random.key(0))
    cm = CostModel(cfg, chips=1)
    pts: list[CalibrationPoint] = []
    for length in prefill_lens:
        cache = models.init_cache(cfg, 1, length)
        tokens = jnp.zeros((1, length), jnp.int32)
        fn = jax.jit(lambda p, t, c, _cfg=cfg: models.prefill(p, _cfg, t, c))
        t = _time_step(fn, params, tokens, cache, reps=reps)
        flops, bytes_ = cm.prefill_cost(length)
        pts.append(CalibrationPoint("prefill", 1, length, flops, bytes_, t))
    for batch, ctx in decode_grid:
        cache = models.init_cache(cfg, batch, ctx)
        tokens = jnp.zeros((batch, 1), jnp.int32)
        fn = jax.jit(
            lambda p, t, c, _cfg=cfg: models.decode_step(p, _cfg, t, c))
        t = _time_step(fn, params, tokens, cache, reps=reps)
        flops, bytes_ = cm.decode_cost(batch, ctx)
        pts.append(CalibrationPoint("decode", batch, ctx, flops, bytes_, t))
    return pts


def calibrate_config(cfg: ModelConfig, out_dir: Path, rep: Report,
                     smoke: bool = False) -> Path:
    """Measure, fit, persist and report one model config.  Returns the
    CALIB artifact path."""
    lens = PREFILL_LENS_SMOKE if smoke else PREFILL_LENS
    grid = DECODE_GRID_SMOKE if smoke else DECODE_GRID
    backend = jax.default_backend()
    pts = measure_points(cfg, lens, grid, reps=3 if smoke else 5)
    calib = calibrate(cfg.name, backend, pts, chips=1)
    path = save_calibration(calib, Path(out_dir) / f"CALIB_{cfg.name}.json")
    for p, err in zip(calib.points, calib.rel_errors()):
        rep.add(f"calibrate.{cfg.name}.{p.kind}.b{p.batch}c{p.context}",
                measured_us=f"{p.measured_s*1e6:.1f}",
                predicted_us=f"{calib.predict(p)*1e6:.1f}",
                rel_err=f"{err:.3f}")
    rep.add(f"calibrate.{cfg.name}.fit",
            backend=backend,
            flops_scale=f"{calib.flops_scale:.3g}",
            bytes_scale=f"{calib.bytes_scale:.3g}",
            step_overhead_us=f"{calib.step_overhead*1e6:.1f}",
            max_rel_err=f"{calib.max_rel_err:.3f}",
            tolerance=calib.tolerance,
            within_tolerance=calib.within_tolerance,
            artifact=str(path))
    return path


def main(smoke: bool = False, out_dir: str = "artifacts/bench",
         report: Report | None = None) -> Report:
    rep = report or Report("calibrate: measured roofline fit")
    kernel_correctness(rep)
    for cfg in ([TINY] if smoke else [TINY, SMALL]):
        calibrate_config(cfg, Path(out_dir), rep, smoke=smoke)
    rep.note(f"backend={jax.default_backend()}: on the CPU container the "
             "fit absorbs XLA-CPU throughput into flops/bytes scales; on "
             "TPU the same harness calibrates against real step times")
    rep.note("CALIB_<model>.json feeds CostModel.from_calibration — the "
             "sim plane's step times then come from measurement, not "
             "hand-set constants")
    return rep


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out-dir", default="artifacts/bench")
    a = ap.parse_args()
    print(main(smoke=a.smoke, out_dir=a.out_dir).render())
