"""Paged flash-decoding over a block-table-indirected KV pool — Pallas
TPU kernel (the measured fast path behind serving/kv_cache.py).

The KV cache lives in a shared pool of fixed-size pages — the same
128-token pages ``PageAllocator`` accounts for — instead of per-sequence
contiguous rings.  Each sequence names its pages through a **block
table**: row ``b`` lists the physical page ids holding that sequence's
context, in logical order (shared prefix blocks first, then private
pages; -1 pads the tail).  Two sequences sharing a cached prefix simply
list the same physical page ids, so the prefix-cache plane's
"cached context is KV-reads-not-recompute" pricing is realized as an
actual memory-access pattern: one copy of the prefix in HBM, gathered by
every sharer.

The gather is the grid itself: ``PrefetchScalarGridSpec`` prefetches the
block table and context lengths into SMEM before the kernel runs, and
the K/V ``BlockSpec`` index maps read ``bt[b, j]`` to aim each grid
step's DMA at the right physical page — no materialized per-sequence
copy ever exists.  Softmax streams over pages with the usual
(m, l, acc) running max/sum rescaling in VMEM scratch.

Layout: q (B, Hkv, G, dh) — the whole GQA query group rides the MXU
tile; k_pages, v_pages (Hkv, N_pages, page, dh) — a page is the
second-to-last (sublane) axis so each block is a well-tiled
(page × dh) slab; block_tables (B, P) int32; ctx_lens (B,) int32
(number of valid cached tokens; position ``ctx_len - 1`` is the newest).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, window: int):
    b = pl.program_id(0)
    jp = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(jp == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dh = q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * (1.0 / math.sqrt(dh))  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                          # (page, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, page)
    # token positions this logical page covers; unmapped tail pages
    # (block-table -1, clamped to page 0 by the index map) fall past
    # ctx_len and mask out here
    kpos = jp * page + jax.lax.broadcasted_iota(jnp.int32, (page,), 0)
    ctx = len_ref[b]
    valid = kpos < ctx
    if window > 0:
        valid &= kpos >= ctx - window
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

    @pl.when(jp == np_ - 1)
    def _fin():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           ctx_lens: jax.Array, *, window: int = -1,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, dh); k_pages, v_pages: (Hkv, N, page, dh);
    block_tables: (B, P) int32, -1 = unmapped; ctx_lens: (B,) int32.
    Returns (B, Hkv, G, dh)."""
    b, hkv, g, dh = q.shape
    page = k_pages.shape[2]
    npages = block_tables.shape[1]

    def q_map(b_, h_, j, bt_ref, len_ref):
        return (b_, h_, 0, 0)

    def kv_map(b_, h_, j, bt_ref, len_ref):
        # the paged gather: logical page j of sequence b_ lives at
        # physical page bt[b_, j]; -1 (tail padding) clamps to page 0,
        # whose keys the kernel masks out via ctx_lens
        return (h_, jnp.maximum(bt_ref[b_, j], 0), 0, 0)

    kern = functools.partial(_kernel, page=page, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, npages),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), q_map),
            pl.BlockSpec((1, 1, page, dh), kv_map),
            pl.BlockSpec((1, 1, page, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      q, k_pages, v_pages)
