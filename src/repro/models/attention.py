"""Attention: GQA, sliding windows, query-chunking, ring-buffer KV caches.

Two execution paths share one masked-softmax core:

* ``attention_full``  — whole-sequence (training / one-shot prefill).  The
  query axis is processed in ``cfg.attn_chunk`` chunks via ``lax.scan`` so
  the score tensor never materializes at (S × S); windowed layers
  additionally ``dynamic_slice`` the K/V stream to ``window + chunk``
  keys per query chunk, which is what makes 32k-prefill local layers and
  500k SWA decoding sub-quadratic in both FLOPs and bytes.

* ``attention_cached`` — attend a (short) query block against a ring-buffer
  KV cache (chunked prefill steps and decode).  The cache stores absolute
  key positions (``kpos``), so sliding-window masks, ring wraparound and
  not-yet-written slots all reduce to one position comparison.

The pure-jnp path here is also the oracle for the Pallas kernels in
``repro.kernels`` (see kernels/ref.py), and is what the dry-run lowers so
``cost_analysis()`` sees real FLOPs (a pallas custom-call would hide them).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import FULL_ATTENTION, BlockSpec, ModelConfig
from repro.models.layers import apply_rope
from repro.models.params import P, tp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter defs
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    defs = {
        "wq": P((d, h, dh), ("embed", "heads", None), fan_in=d),
        "wk": P((d, hkv, dh), ("embed", "kv_heads", None), fan_in=d),
        "wv": P((d, hkv, dh), ("embed", "kv_heads", None), fan_in=d),
        "wo": P((h, dh, d), ("heads", None, "embed"), fan_in=h * dh),
    }
    if cfg.qk_norm and not cross:
        defs["q_scale"] = P((dh,), (None,), init="ones", dtype="float32")
        defs["k_scale"] = P((dh,), (None,), init="ones", dtype="float32")
    return defs


# ---------------------------------------------------------------------------
# Core masked attention (GQA grouped layout)
# ---------------------------------------------------------------------------


def _group(q: jax.Array, hkv: int) -> jax.Array:
    b, s, h, dh = q.shape
    return q.reshape(b, s, hkv, h // hkv, dh)


def _qk_rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(dt)


def attn_core(q: jax.Array, k: jax.Array, v: jax.Array,
              mask: jax.Array) -> jax.Array:
    """q: (B,Sq,Hkv,G,dh); k,v: (B,T,Hkv,dh); mask: (B,1,1,Sq,T) or
    broadcastable.  Returns (B,Sq,Hkv,G,dh)."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhgd,bthd->bhgqt", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(dh))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Full-sequence path (training / one-shot prefill)
# ---------------------------------------------------------------------------


def _causal_window_mask(qpos: jax.Array, kpos: jax.Array,
                        window: int) -> jax.Array:
    """qpos (Sq,), kpos (T,) -> (1,1,1,Sq,T) bool."""
    m = kpos[None, :] <= qpos[:, None]
    if window > 0:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m[None, None, None]


def attention_full(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   window: int, chunk: int, causal: bool = True) -> jax.Array:
    """q (B,S,H,dh) vs k,v (B,T,Hkv,dh), queries chunked by ``chunk``."""
    b, s, h, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    qg = _group(q, hkv)

    if not causal:  # encoder self-attention / cross-attention
        mask = jnp.ones((1, 1, 1, s, t), bool)
        out = attn_core(qg, k, v, mask)
        return out.reshape(b, s, h, dh)

    if s <= chunk or s % chunk != 0:
        # irregular lengths (engine ensures multiples of chunk on hot paths)
        mask = _causal_window_mask(jnp.arange(s), jnp.arange(t), window)
        return attn_core(qg, k, v, mask).reshape(b, s, h, dh)
    n_chunks = s // chunk
    use_slice = window > 0 and t > window + chunk
    kv_span = window + chunk if use_slice else t

    def body(carry, i):
        qs = i * chunk
        qc = jax.lax.dynamic_slice_in_dim(qg, qs, chunk, axis=1)
        if use_slice:
            ks = jnp.clip(qs - window, 0, t - kv_span)
            kc = jax.lax.dynamic_slice_in_dim(k, ks, kv_span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ks, kv_span, axis=1)
            kpos = ks + jnp.arange(kv_span)
        else:
            kc, vc, kpos = k, v, jnp.arange(t)
        qpos = qs + jnp.arange(chunk)
        mask = _causal_window_mask(qpos, kpos, window)
        oc = attn_core(qc, kc, vc, mask)
        return carry, oc

    _, chunks = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # chunks: (n_chunks, B, chunk, Hkv, G, dh) -> (B, S, H, dh)
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, s, hkv, h // hkv, dh)
    return out.reshape(b, s, h, dh)


# ---------------------------------------------------------------------------
# Ring-buffer KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Ring buffer over ``size`` slots; ``kpos`` holds the absolute position
    written in each slot (-1 = empty).  For full-attention layers ``size``
    equals the max context so the ring never wraps; for SWA layers
    ``size = window + chunk`` rounded up, bounding cache memory AND the
    bytes each decode step reads — the TPU-adapted equivalent of the
    paper's bounded-cache serving assumption."""

    k: jax.Array       # (B, size, Hkv, dh)
    v: jax.Array       # (B, size, Hkv, dh)
    kpos: jax.Array    # (B, size) int32


def kv_cache_size(spec: BlockSpec, max_context: int, chunk: int) -> int:
    if spec.window > 0:
        size = spec.window + chunk
        return min(-(-size // chunk) * chunk, max_context)
    return max_context


def init_kv_cache(batch: int, size: int, hkv: int, dh: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, size, hkv, dh), dtype),
        v=jnp.zeros((batch, size, hkv, dh), dtype),
        kpos=jnp.full((batch, size), -1, jnp.int32),
    )


def cache_write(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                start_pos: jax.Array) -> KVCache:
    """Write S_new tokens at absolute positions start_pos..start_pos+S_new.

    start_pos: (B,) int32.  If S_new exceeds the ring size only the last
    ``size`` tokens are written (the older ones would be overwritten
    anyway); this keeps scatter slots unique.
    """
    b, s_new = k_new.shape[:2]
    size = cache.k.shape[1]
    if s_new > size:
        k_new = k_new[:, s_new - size:]
        v_new = v_new[:, s_new - size:]
        start_pos = start_pos + (s_new - size)
        s_new = size
    pos = start_pos[:, None] + jnp.arange(s_new)[None, :]        # (B, S_new)
    slots = pos % size
    bidx = jnp.arange(b)[:, None]
    k = cache.k.at[bidx, slots].set(k_new)
    v = cache.v.at[bidx, slots].set(v_new)
    kpos = cache.kpos.at[bidx, slots].set(pos)
    return KVCache(k, v, kpos)


def _cached_mask(kpos: jax.Array, q_pos: jax.Array,
                 window: int) -> jax.Array:
    """kpos (B,size), q_pos (B,Sq) -> (B,1,1,Sq,size)."""
    mask = (kpos[:, None, :] <= q_pos[:, :, None]) & (kpos[:, None, :] >= 0)
    if window > 0:
        mask &= kpos[:, None, :] > (q_pos[:, :, None] - window)
    return mask[:, None, None]


def attention_cached(q: jax.Array, cache: KVCache, q_pos: jax.Array, *,
                     window: int, chunk: int = 0) -> jax.Array:
    """q: (B,Sq,H,dh) at absolute positions q_pos (B,Sq).  Assumes the
    q tokens' own K/V were already written (write-then-attend).  Large Sq
    is processed in ``chunk``-sized query blocks."""
    b, sq, h, dh = q.shape
    hkv = cache.k.shape[2]
    qg = _group(q, hkv)

    if chunk and sq > chunk and sq % chunk == 0:
        nc = sq // chunk

        def body(_, i):
            qc = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
            pc = jax.lax.dynamic_slice_in_dim(q_pos, i * chunk, chunk, axis=1)
            oc = attn_core(qc, cache.k, cache.v,
                           _cached_mask(cache.kpos, pc, window))
            return _, oc

        _, chunks = jax.lax.scan(body, None, jnp.arange(nc))
        out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, hkv, h // hkv, dh)
        return out.reshape(b, sq, h, dh)

    out = attn_core(qg, cache.k, cache.v,
                    _cached_mask(cache.kpos, q_pos, window))
    return out.reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# Paged-pool KV cache (the measured fast path)
# ---------------------------------------------------------------------------


class PagedKVCache(NamedTuple):
    """Shared KV page pool for one layer: ``(num_pages + 1, Hkv, dh,
    page)``, tokens along the minor axis, so a page is one contiguous
    slab, a head size such as 120 tiles without lane padding, and the
    Pallas kernel reads the pool as stored.  Physical page ids come from
    ``serving.kv_cache.PageAllocator`` — page ``i`` of the pool IS
    allocator page ``i``, so the scheduling
    plane's accounting and the attention memory layout are one structure,
    and sequences acquiring a shared prefix block attend through the
    *same* physical pages with zero KV copies.

    The extra last page is a write sink: batch rows whose block-table
    entry is -1 (inactive slots, positions past the mapped tail) scatter
    there instead of corrupting page 0.  Reads clamp -1 to page 0 and
    mask by position, matching the Pallas kernel's contract."""

    k: jax.Array       # (num_pages + 1, Hkv, dh, page)
    v: jax.Array       # (num_pages + 1, Hkv, dh, page)


def init_paged_kv_cache(num_pages: int, page: int, hkv: int, dh: int,
                        dtype) -> PagedKVCache:
    return PagedKVCache(
        k=jnp.zeros((num_pages + 1, hkv, dh, page), dtype),
        v=jnp.zeros((num_pages + 1, hkv, dh, page), dtype),
    )


def _phys_slots(cache: PagedKVCache, tables: jax.Array,
                pos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Map absolute token positions (B,S) through block tables (B,P) to
    (physical page, in-page slot); unmapped positions hit the sink."""
    page = cache.k.shape[-1]
    p_max = tables.shape[1]
    sink = cache.k.shape[0] - 1
    logical = pos // page
    phys = jnp.take_along_axis(tables, jnp.clip(logical, 0, p_max - 1),
                               axis=1)
    bad = (phys < 0) | (logical < 0) | (logical >= p_max)
    return jnp.where(bad, sink, phys), pos % page


def paged_cache_fill(cache: PagedKVCache, k_new: jax.Array,
                     v_new: jax.Array, pos: jax.Array,
                     tables: jax.Array) -> PagedKVCache:
    """Write each row's tokens at consecutive positions ``pos[:, 0] +
    s`` (a -1 tail marks padding, a -1 row writes nothing) — the
    engine's write path.  Whole pages are gathered, filled and scattered
    back, so no write indexes the pool's minor (token) axis and the
    compiler keeps the pool as stored, with no copy of it around the
    write.  Live rows own their mapped pages exclusively, so page
    writes never collide; pages no token lands in go to the sink."""
    b, s = pos.shape
    hkv, dh, page = cache.k.shape[1:]
    p_max = tables.shape[1]
    sink = cache.k.shape[0] - 1
    n_pg = (s - 2) // page + 2          # pages s consecutive tokens span
    start = pos[:, :1]
    logical = jnp.maximum(start, 0) // page + jnp.arange(n_pg)  # (B, n_pg)
    # the token that lands in slot t of page j, and whether it is real
    tok = (logical[:, :, None] * page + jnp.arange(page)
           - start[:, :, None]).reshape(b, -1)            # (B, n_pg·page)
    src = jnp.clip(tok, 0, s - 1)
    real = ((tok >= 0) & (tok < s)
            & (jnp.take_along_axis(pos, src, axis=1) >= 0))
    real = real.reshape(b, n_pg, 1, 1, page)
    phys = jnp.take_along_axis(tables, jnp.minimum(logical, p_max - 1),
                               axis=1)
    live = (logical < p_max) & (phys >= 0) & real.any(axis=(2, 3, 4))
    dest = jnp.where(live, phys, sink)

    def fill(pool, new):
        slab = jnp.take_along_axis(new, src[:, :, None, None], axis=1)
        slab = jnp.moveaxis(slab.reshape(b, n_pg, page, hkv, dh), 2, -1)
        return pool.at[dest].set(
            jnp.where(real, slab.astype(pool.dtype), pool[dest]))

    return PagedKVCache(fill(cache.k, k_new), fill(cache.v, v_new))


def paged_cache_write_at(cache: PagedKVCache, k_new: jax.Array,
                         v_new: jax.Array, pos: jax.Array,
                         tables: jax.Array) -> PagedKVCache:
    """Scatter tokens at explicit absolute positions (B, S); negative
    positions (unwritten ring slots during KV injection) hit the sink.
    Tokens are scattered one by one along the pool's token axis, which
    the compiler may serve with a relaid copy of the pool: the engine's
    step writes through ``paged_cache_fill``."""
    phys, slot = _phys_slots(cache, tables, pos)
    k = cache.k.at[phys, :, :, slot].set(k_new)
    v = cache.v.at[phys, :, :, slot].set(v_new)
    return PagedKVCache(k, v)


def paged_view(cache: PagedKVCache, tables: jax.Array) -> KVCache:
    """Gather a (B, P·page) contiguous view of each sequence's pages —
    the pure-jnp oracle for the Pallas paged kernel, and the prefill
    path (the kernel is decode-only).  Returned as a ring-layout
    ``KVCache`` so the masked-softmax core is shared: ``kpos`` is the
    absolute position (logical index) for mapped pages, -1 for the
    unmapped tail."""
    b, p_max = tables.shape
    hkv, dh, page = cache.k.shape[1:]
    phys = jnp.maximum(tables, 0)

    def gather(pool):                   # (B, P, Hkv, dh, page) -> tokens
        return jnp.moveaxis(pool[phys], 4, 2).reshape(b, p_max * page,
                                                      hkv, dh)

    kg, vg = gather(cache.k), gather(cache.v)
    t = p_max * page
    kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kpos = jnp.where(jnp.repeat(tables >= 0, page, axis=1), kpos, -1)
    return KVCache(kg, vg, kpos)


def self_attention_paged(params: dict, x: jax.Array, cache: PagedKVCache,
                         cfg: ModelConfig, spec: BlockSpec,
                         positions: jax.Array, tables: jax.Array,
                         ) -> tuple[jax.Array, PagedKVCache]:
    """Write-then-attend over the shared page pool.  Serves both decode
    (Sq = 1) and suffix prefill (Sq = uncached prompt tokens, attending
    back into prefix pages a sibling request already populated).

    Decode with ``cfg.use_pallas`` runs the Pallas paged kernel
    (``ops.paged_decode_attention``) straight over the pool + live block
    tables; everything else uses the jnp gather oracle, which is also
    the interpret-parity reference the tests pin the kernel against."""
    q, k, v = qkv_project(params, x, cfg, positions)
    pos1 = _pos1d(positions)
    # write through the explicit per-token positions (consecutive from
    # each row's first for prefill and decode) so a padded mixed-step
    # chunk can mark its tail -1: those writes route to the sink page
    # instead of scribbling past the valid frontier of the sequence's
    # pages
    cache = paged_cache_fill(cache, k, v, pos1, tables)
    sq = q.shape[1]
    if cfg.use_pallas and sq == 1:
        from repro.kernels import ops
        # write-then-attend: the just-written token is position pos, so
        # ctx = pos + 1; rows with an unmapped head page are inactive
        # padding slots — zero context (the kernel emits zeros there)
        ctx = jnp.where(tables[:, 0] >= 0, pos1[:, 0] + 1, 0)
        out = ops.paged_decode_attention(q, cache.k, cache.v, tables, ctx,
                                         window=spec.window)
    else:
        view = paged_view(cache, tables)
        out = attention_cached(q, view, pos1, window=spec.window,
                               chunk=cfg.attn_chunk)
    return out_project(params, out), cache


# ---------------------------------------------------------------------------
# Full attention block entry points (proj + rope + core + out-proj)
# ---------------------------------------------------------------------------


def qkv_project(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, tp(params["wq"], None, "model", None))
    k = jnp.einsum("bsd,dhk->bshk", x, tp(params["wk"], None, "model", None))
    v = jnp.einsum("bsd,dhk->bshk", x, tp(params["wv"], None, "model", None))
    if cfg.qk_norm and "q_scale" in params:
        q = _qk_rms(q, params["q_scale"], cfg.norm_eps)
        k = _qk_rms(k, params["k_scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def out_project(params: dict, out: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", out, tp(params["wo"], "model", None, None))


def _pos1d(positions: jax.Array) -> jax.Array:
    """(B,S) from (B,S) or (B,S,3) (M-RoPE uses the temporal stream for
    cache bookkeeping)."""
    return positions[..., 0] if positions.ndim == 3 else positions


def self_attention(params: dict, x: jax.Array, cfg: ModelConfig,
                   spec: BlockSpec, positions: jax.Array,
                   causal: bool = True) -> jax.Array:
    """Whole-sequence self attention (train / one-shot prefill)."""
    q, k, v = qkv_project(params, x, cfg, positions)
    out = attention_full(q, k, v, window=spec.window, chunk=cfg.attn_chunk,
                         causal=causal)
    return out_project(params, out)


def self_attention_cached(params: dict, x: jax.Array, cache: KVCache,
                          cfg: ModelConfig, spec: BlockSpec,
                          positions: jax.Array) -> tuple[jax.Array, KVCache]:
    """Write this block of tokens into the ring cache, then attend.
    Valid for decode (Sq=1) and chunked-prefill steps (Sq <= cache slack)."""
    q, k, v = qkv_project(params, x, cfg, positions)
    pos1 = _pos1d(positions)
    cache = cache_write(cache, k, v, pos1[:, 0])
    out = attention_cached(q, cache, pos1, window=spec.window,
                           chunk=cfg.attn_chunk)
    return out_project(params, out), cache


def self_attention_prefill(params: dict, x: jax.Array, cache: KVCache,
                           cfg: ModelConfig, spec: BlockSpec,
                           positions: jax.Array) -> tuple[jax.Array, KVCache]:
    """One-shot prefill from position 0: windowed/chunked full attention
    over the prompt itself, then write the surviving tail into the ring."""
    q, k, v = qkv_project(params, x, cfg, positions)
    out = attention_full(q, k, v, window=spec.window, chunk=cfg.attn_chunk)
    cache = cache_write(cache, k, v, _pos1d(positions)[:, 0])
    return out_project(params, out), cache


def cross_attention(params: dict, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """Encoder-decoder cross attention (memory precomputed)."""
    q = jnp.einsum("bsd,dhk->bshk", x, tp(params["wq"], None, "model", None))
    k = jnp.einsum("btd,dhk->bthk", memory, tp(params["wk"], None, "model", None))
    v = jnp.einsum("btd,dhk->bthk", memory, tp(params["wv"], None, "model", None))
    out = attention_full(q, k, v, window=FULL_ATTENTION,
                         chunk=cfg.attn_chunk, causal=False)
    return out_project(params, out)


class CrossKV(NamedTuple):
    k: jax.Array   # (B, T_enc, Hkv, dh)
    v: jax.Array


def cross_kv_precompute(params: dict, memory: jax.Array) -> CrossKV:
    k = jnp.einsum("btd,dhk->bthk", memory, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", memory, params["wv"])
    return CrossKV(k, v)


def cross_attention_cached(params: dict, x: jax.Array,
                           ckv: CrossKV) -> jax.Array:
    b, sq = x.shape[:2]
    t = ckv.k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    hkv = ckv.k.shape[2]
    mask = jnp.ones((1, 1, 1, sq, t), bool)
    out = attn_core(_group(q, hkv), ckv.k, ckv.v, mask)
    return out_project(params, out.reshape(b, sq, q.shape[2], q.shape[3]))
