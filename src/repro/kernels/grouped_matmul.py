"""Grouped (per-expert) matmul — Pallas TPU kernel for the MoE layer.

Computes out[e] = x[e] @ w[e] for the (E, C, d) dispatch buffer produced
by models/moe.py's sort-based routing.  Grid (E, nc, nf, nd) accumulates
over the contraction axis in VMEM f32 scratch; experts whose row count is
zero (``counts``) skip the MXU entirely — the TPU equivalent of
megablocks' ragged skip, which is where the kernel beats a dense
einsum when expert load is skewed.

Layout: x (E, C, d); w (E, d, f); counts (E,) int32 (rows actually
occupied per expert; C-padded rows are zeros either way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(counts_ref, x_ref, w_ref, o_ref, acc_ref, *,
            blk_c: int):
    e = pl.program_id(0)
    ic = pl.program_id(1)
    kd = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    count = counts_ref[e]
    live = ic * blk_c < count

    @pl.when(live)
    def _body():
        x = x_ref[0].astype(jnp.float32)          # (blk_c, blk_d)
        w = w_ref[0].astype(jnp.float32)          # (blk_d, blk_f)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _fin():
        # zero rows past this expert's live count (padding rows must not
        # leak garbage even if the dispatch buffer wasn't pre-zeroed)
        rows = ic * blk_c + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        acc = jnp.where(rows < count, acc_ref[...], 0.0)
        o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_c", "blk_f", "blk_d",
                                             "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, counts: jax.Array, *,
                   blk_c: int = 128, blk_f: int = 128, blk_d: int = 128,
                   interpret: bool = False) -> jax.Array:
    """x: (E, C, d) @ w: (E, d, f) -> (E, C, f), skipping empty experts."""
    e, c, d = x.shape
    f = w.shape[2]
    blk_c, blk_f, blk_d = min(blk_c, c), min(blk_f, f), min(blk_d, d)
    grid = (e, c // blk_c, f // blk_f, d // blk_d)

    kern = functools.partial(_kernel, blk_c=blk_c)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # counts, whole array
            pl.BlockSpec((1, blk_c, blk_d),
                         lambda e_, i, j, k_: (e_, i, k_)),
            pl.BlockSpec((1, blk_d, blk_f),
                         lambda e_, i, j, k_: (e_, k_, j)),
        ],
        out_specs=pl.BlockSpec((1, blk_c, blk_f),
                               lambda e_, i, j, k_: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((blk_c, blk_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(counts, x, w)
