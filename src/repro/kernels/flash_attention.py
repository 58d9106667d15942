"""Blocked causal flash attention (prefill/training) — Pallas TPU kernel.

Grid (B, H, nq, nk); the nk axis iterates sequentially per (b, h, iq) with
the running (m, l, acc) streaming-softmax state held in VMEM scratch —
the TPU-native restatement of flash attention (no warp shuffles; the MXU
consumes (blk_q × dh) · (dh × blk_k) tiles, dh padded to a lane multiple
of 128 by ops.py).

Supports GQA (q head h reads kv head h // group via the k/v index_map)
and sliding windows (fully-masked k-blocks are skipped with ``pl.when``,
so SWA costs O(S·window) not O(S²)).

Layout (from ops.py): q (B, H, S, dh); k, v (B, Hkv, T, dh).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            blk_q: int, blk_k: int, kv_len: int, window: int, causal: bool,
            scale: float):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * blk_q
    k_start = jk * blk_k
    # block-level skip: fully-masked k blocks never touch the MXU
    live = k_start < kv_len
    if causal:
        live &= k_start <= q_start + blk_q - 1
        if window > 0:
            live &= (k_start + blk_k - 1) > (q_start - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, dh)
        k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # (blk_q, blk_k)
        v = v_ref[0, 0].astype(jnp.float32)                  # (blk_k, dh)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(jk == nk - 1)
    def _fin():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = -1,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, dh); k, v: (B, Hkv, T, dh).  S, T multiples of the
    block sizes and dh lane-aligned (ops.py pads).  Returns (B, H, S, dh)."""
    b, h, s, dh = q.shape
    _, hkv, t, _ = k.shape
    group = h // hkv
    nq, nk = s // blk_q, t // blk_k
    scale = 1.0 / math.sqrt(dh)

    kern = functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, kv_len=t,
                             window=window, causal=causal, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, blk_k, dh),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, blk_k, dh),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
