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

The pool is stored tokens-minor, ``(N, Hkv, dh, page)``: each page is
one contiguous slab holding every KV head, with the page's tokens along
the 128-wide lane axis.  The head size lies on sublanes, where danube's
120 tiles without padding (on lanes it would pad to 128), so the
compiler keeps the pool as stored and the kernel reads it in place —
no relayout of the pool feeds the call.

The grid is (row, block): a row's live logical pages, from the first one
its window admits to ``ceil(ctx_len / page)``, are taken
``pages_per_block`` at a time.  The pool is passed once per page of a
block; each such input's ``BlockSpec`` is one physical page's slab,
picked by an index map that reads the block table
``PrefetchScalarGridSpec`` put in SMEM.  Once a row has no more pages
for an input, its index stays where it was, so the pipeline copies
nothing for the row's remaining grid steps, and it is already fetching
the next row's first block while the current one is computed.  Rows with
``ctx_len == 0`` compute nothing and write zeros.

Per KV head, the head's G query heads score against the slab's
``(dh, page)`` keys (``q @ k``) and take ``p @ v`` against its
``(dh, page)`` values.  Softmax streams over blocks with the usual
(m, l, acc) running max/sum rescaling in VMEM scratch; scores,
statistics and the accumulator are float32.

Layout: q (B, Hkv, G, dh); k_pages, v_pages (N_pages, Hkv, dh, page),
the pool as stored; block_tables (B, P) int32; ctx_lens (B,) int32
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
# VMEM the pages of one block of K (or of V) may take; the pipeline
# double-buffers both, so four such blocks are live at once
BLOCK_BYTES = 1 << 20


def slab_bytes(hkv: int, dh: int, page: int, itemsize: int) -> int:
    """VMEM one ``(Hkv, dh, page)`` page slab takes, counted at the
    (sublane, 128-lane) tiles VMEM stores it in."""
    sublanes = 32 // itemsize
    return (hkv * -(-dh // sublanes) * sublanes * -(-page // 128) * 128
            * itemsize)


def pages_per_block(hkv: int, dh: int, page: int, itemsize: int,
                    npages: int) -> int:
    """Pages one grid step takes: as many slabs as fit ``BLOCK_BYTES``,
    at least one and at most the table's ``npages``."""
    slab = slab_bytes(hkv, dh, page, itemsize)
    return max(1, min(npages, BLOCK_BYTES // slab))


def _span(ctx, *, page: int, window: int):
    """Logical pages [first, end) holding the keys a row of ``ctx``
    cached tokens attends to."""
    first = jnp.maximum(ctx - window, 0) // page if window > 0 else 0
    return first, (ctx + page - 1) // page


def _kernel(bt_ref, len_ref, q_ref, *refs, window: int, nb: int):
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * nb:]
    b, j = pl.program_id(0), pl.program_id(1)
    hkv, _, dh = q_ref.shape[1:]
    page = k_refs[0].shape[-1]
    ctx = len_ref[b]
    first, end = _span(ctx, page=page, window=window)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first + j * nb < end)
    def _block():
        # slabs past the row's last live page hold some other page and
        # mask out here, with positions past ctx_len
        col = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        valid = []
        for i in range(nb):
            kpos = (first + j * nb + i) * page + col
            ok = kpos < ctx
            if window > 0:
                ok &= kpos >= ctx - window
            valid.append(ok)
        for h in range(hkv):
            q = q_ref[0, h]                                       # (G, dh)
            scores = [
                jnp.where(valid[i], jax.lax.dot_general(
                    q, k_refs[i][0, h], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) / math.sqrt(dh),
                    NEG_INF)
                for i in range(nb)]                               # (G, page)
            m_prev = m_ref[h]
            m_new = functools.reduce(
                jnp.maximum,
                [jnp.max(s, axis=1, keepdims=True) for s in scores], m_prev)
            alpha = jnp.exp(m_prev - m_new)
            l_sum = alpha * l_ref[h]
            acc = alpha * acc_ref[h]
            for i, s in enumerate(scores):
                p = jnp.exp(s - m_new)
                l_sum += jnp.sum(p, axis=1, keepdims=True)
                acc += jax.lax.dot_general(
                    p, v_refs[i][0, h].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)           # (G, dh)
            m_ref[h] = m_new
            l_ref[h] = l_sum
            acc_ref[h] = acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           ctx_lens: jax.Array, *, window: int = -1,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, dh); k_pages, v_pages: (N, Hkv, dh, page);
    block_tables: (B, P) int32, -1 = unmapped; ctx_lens: (B,) int32.
    Returns (B, Hkv, G, dh)."""
    b, hkv, g, dh = q.shape
    page = k_pages.shape[-1]
    npages = block_tables.shape[1]
    nb = pages_per_block(hkv, dh, page, k_pages.dtype.itemsize, npages)
    span = functools.partial(_span, page=page, window=window)

    def row_map(b_, j, bt_ref, len_ref):
        return (b_, 0, 0, 0)

    def page_map(i):
        def index(b_, j, bt_ref, len_ref):
            # the paged gather: input i of step (b_, j) holds logical
            # page first + j·nb + i of row b_, physical page bt[b_, .];
            # past the row's last page for input i it keeps the last
            # one it held (no copy), and a row with fewer than i + 1
            # pages gives it the row's last page; -1 clamps to page 0,
            # as the reference does
            first, end = span(len_ref[b_])
            last = (end - first - 1 - i) // nb
            p = jnp.where(last >= 0, first + jnp.minimum(j, last) * nb + i,
                          end - 1)
            p = jnp.clip(p, 0, npages - 1)
            return (jnp.maximum(bt_ref[b_ * npages + p], 0), 0, 0, 0)
        return index

    slabs = [pl.BlockSpec((1, hkv, dh, page), page_map(i))
             for i in range(nb)]
    rows = pl.BlockSpec((1, hkv, g, dh), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-npages // nb)),
        in_specs=[rows, *slabs, *slabs],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, window=window, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.reshape(-1).astype(jnp.int32),
      ctx_lens.astype(jnp.int32), q, *[k_pages] * nb, *[v_pages] * nb)
