"""Flash-decoding over a (ring) KV cache — Pallas TPU kernel.

One new query token per sequence attends to the cached keys.  The grid
iterates KV blocks sequentially per (batch, kv-head) with streaming
(m, l, acc) in VMEM scratch; the whole GQA query group (G = H/Hkv rows,
padded to the 8-sublane minimum by ops.py) rides in the MXU tile, so a
128-key block does a (G × dh)·(dh × 128) matmul per step.

Validity comes from the ring cache's ``kpos`` (absolute position per
slot, -1 = empty): mask = 0 <= kpos <= q_pos (and > q_pos - window), so
ring wraparound and partially-filled caches need no special cases —
identical semantics to models/attention.py's cached path.

Layout (from ops.py): q (B, Hkv, G, dh); k, v (B, Hkv, T, dh);
kpos (B, T) int32; q_pos (B, 1) int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, kpos_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, blk_k: int, window: int):
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, dh = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * (1.0 / math.sqrt(dh))  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                          # (blk_k, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, blk_k)
    kpos = kpos_ref[0]                                           # (blk_k,)
    qpos = qpos_ref[0, 0]
    valid = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        valid &= kpos > qpos - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    v = v_ref[0, 0].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(jk == nk - 1)
    def _fin():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "blk_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kpos: jax.Array, q_pos: jax.Array, *,
                     window: int = -1, blk_k: int = 128,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, dh); k, v: (B, Hkv, T, dh); kpos: (B, T);
    q_pos: (B, 1).  Returns (B, Hkv, G, dh)."""
    b, hkv, g, dh = q.shape
    t = k.shape[2]
    # pad the key axis up to a whole number of blocks: the tail block's
    # padded slots carry kpos = -1, which the validity mask already
    # treats as empty — without this, t % blk_k trailing keys would be
    # silently dropped from the softmax
    nk = -(-t // blk_k)
    pad = nk * blk_k - t
    if pad:
        widths4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, widths4)
        v = jnp.pad(v, widths4)
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)

    kern = functools.partial(_kernel, blk_k=blk_k, window=window)
    return pl.pallas_call(
        kern,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, blk_k, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, blk_k), lambda b_, h_, j: (b_, j)),
            pl.BlockSpec((1, 1), lambda b_, h_, j: (b_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, kpos, q_pos)
