"""Model assembly: embeddings → stack-plan segments (nested lax.scan) →
final norm → unembed.  Covers decoder-only LMs, the VLM stub path, and
encoder-decoder models, with train / prefill / decode_step entry points.

Layer stacks execute as ``lax.scan`` over stacked parameters so compile
time scales with the number of *distinct block types*, not layers — a
126-layer llama3-405b lowers as one scan.  ``cfg.remat`` wraps the scan
body in ``jax.checkpoint`` (nothing saved inside a layer), the standard
memory/recompute trade recorded in the roofline's MODEL_FLOPS/HLO_FLOPs
ratio.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.models import params as prm
from repro.models.blocks import (block_apply, block_defs, init_block_cache,
                                 init_paged_block_cache)
from repro.models.layers import (chunked_unembed_xent, embed, embed_defs,
                                 rmsnorm, rmsnorm_defs, softmax_xent,
                                 unembed, unembed_defs, unembed_tied)

# ---------------------------------------------------------------------------
# Defs
# ---------------------------------------------------------------------------


def _segment_defs(cfg: ModelConfig, seg: Segment) -> dict:
    out = {}
    for j, (spec, n) in enumerate(seg.pattern):
        d = block_defs(cfg, spec)
        dims = (seg.repeat, n) if seg.repeat > 1 else (n,)
        out[f"e{j}"] = prm.stack(d, *dims)
    return out


def model_defs(cfg: ModelConfig) -> dict:
    defs: dict[str, Any] = {
        "embed": embed_defs(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_defs(cfg.d_model),
        "decoder": [_segment_defs(cfg, s) for s in cfg.plan()],
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = unembed_defs(cfg.d_model, cfg.vocab)
    if cfg.is_encdec:
        defs["encoder"] = [_segment_defs(cfg, s) for s in cfg.enc_plan()]
        defs["enc_norm"] = rmsnorm_defs(cfg.d_model)
    return defs


def init(cfg: ModelConfig, key: jax.Array):
    return prm.init_params(model_defs(cfg), key, jnp.dtype(cfg.dtype))


def param_count(cfg: ModelConfig) -> int:
    return prm.count_params(model_defs(cfg))


# ---------------------------------------------------------------------------
# Stack execution
# ---------------------------------------------------------------------------


def _constrain_act(x: jax.Array, cfg: ModelConfig,
                   seq_sharded: bool = True) -> jax.Array:
    """Activation sharding constraints (no-op without a mesh or when
    ``cfg.act_sharding`` is off).

    ``seq_sharded=True`` — layer-BOUNDARY layout: batch over data axes
    and, Megatron-style sequence parallelism, seq over ``model``: the
    per-layer residuals saved for backward shrink by the TP degree.

    ``seq_sharded=False`` — block-INTERIOR layout: seq gathered (batch
    over data only).  Inside a block the weights are TP-sharded over
    ``model``; if the sequence were too, GSPMD resolves the conflict by
    all-gathering the *weights* (a full 53k×16k w_out per layer for
    llama3-405b).  Gathering the (much smaller) activations instead is
    exactly the Megatron-SP schedule; remat recomputes the gather in
    the backward pass."""
    if not cfg.act_sharding or x.ndim != 3 or x.shape[1] == 1:
        return x
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or not mesh.axis_names:
            return x
        names = set(mesh.axis_names)
        b = tuple(a for a in ("pod", "data")
                  if a in names and x.shape[0] % mesh.shape[a] == 0)
        s = "model" if (seq_sharded and "model" in names
                        and x.shape[1] % mesh.shape["model"] == 0) else None
        if not b and s is None:
            return x
        spec = jax.sharding.PartitionSpec(b if len(b) > 1 else
                                          (b[0] if b else None), s, None)
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def _scan_blocks(p_stack, spec, x, cfg, positions, mode, cache_stack, memory,
                 tables=None):
    """Scan over one stacked run of identical blocks.

    ``cfg.scan_layers=False`` unrolls the stack into a python loop —
    mathematically identical, hugely slower to compile, but XLA's
    ``cost_analysis`` counts a while-loop body only ONCE, so the dry-run
    lowers the unrolled form when it needs honest FLOP/collective counts
    (see launch/dryrun.py)."""

    def body_train(xc, p):
        out = block_apply(p, xc, cfg, spec, positions, mode, None, memory)
        # Sequence-parallel boundary: the saved-for-backward residual
        # stack shrinks by the TP degree (16.9 GB -> 1.05 GB/dev for
        # llama3-405b), at the cost of seq<->TP resharding inside each
        # block's backward dots.  Measured against batch-only sharding
        # this wins by ~21 GB/dev (see EXPERIMENTS.md §Perf).
        return _constrain_act(out.x, cfg), out.aux

    def body_cached(xc, xs):
        p, c = xs
        out = block_apply(p, xc, cfg, spec, positions, mode, c, memory,
                          tables)
        return _constrain_act(out.x, cfg), (out.cache, out.aux)

    if mode == "train":
        body = jax.checkpoint(body_train) if cfg.remat else body_train
        if not cfg.scan_layers:
            n = jax.tree.leaves(p_stack)[0].shape[0]
            aux = jnp.zeros((), jnp.float32)
            for i in range(n):
                x, a = body(x, jax.tree.map(lambda t: t[i], p_stack))
                aux += a
            return x, None, aux
        x, auxes = jax.lax.scan(body, x, p_stack)
        return x, None, jnp.sum(auxes)
    if not cfg.scan_layers:
        n = jax.tree.leaves(p_stack)[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        caches = []
        for i in range(n):
            x, (c, a) = body_cached(
                x, (jax.tree.map(lambda t: t[i], p_stack),
                    jax.tree.map(lambda t: t[i], cache_stack)))
            caches.append(c)
            aux += a
        stacked = jax.tree.map(lambda *cs: jnp.stack(cs), *caches)
        return x, stacked, aux
    x, (caches, auxes) = jax.lax.scan(body_cached, x, (p_stack, cache_stack))
    return x, caches, jnp.sum(auxes)


def _run_segment(seg_params, seg: Segment, x, cfg, positions, mode,
                 seg_cache, memory, tables=None):
    aux_total = jnp.zeros((), jnp.float32)

    if seg.repeat == 1:
        new_cache = {}
        for j, (spec, n) in enumerate(seg.pattern):
            c = seg_cache[f"e{j}"] if seg_cache is not None else None
            x, nc, aux = _scan_blocks(seg_params[f"e{j}"], spec, x, cfg,
                                      positions, mode, c, memory, tables)
            new_cache[f"e{j}"] = nc
            aux_total += aux
        return x, (new_cache if mode != "train" else None), aux_total

    # nested: outer scan over `repeat`, inner scans over each element
    def outer_train(xc, ps):
        aux = jnp.zeros((), jnp.float32)
        for j, (spec, n) in enumerate(seg.pattern):
            xc, _, a = _scan_blocks(ps[f"e{j}"], spec, xc, cfg, positions,
                                    mode, None, memory)
            aux += a
        return xc, aux

    def outer_cached(xc, xs):
        ps, cs = xs
        aux = jnp.zeros((), jnp.float32)
        new_cs = {}
        for j, (spec, n) in enumerate(seg.pattern):
            xc, nc, a = _scan_blocks(ps[f"e{j}"], spec, xc, cfg, positions,
                                     mode, cs[f"e{j}"], memory, tables)
            new_cs[f"e{j}"] = nc
            aux += a
        return xc, (new_cs, aux)

    if not cfg.scan_layers:
        take = lambda tree, r: jax.tree.map(lambda t: t[r], tree)
        if mode == "train":
            for r in range(seg.repeat):
                x, a = outer_train(x, take(seg_params, r))
                aux_total += a
            return x, None, aux_total
        caches = []
        for r in range(seg.repeat):
            x, (c, a) = outer_cached(x, (take(seg_params, r),
                                         take(seg_cache, r)))
            caches.append(c)
            aux_total += a
        stacked = jax.tree.map(lambda *cs: jnp.stack(cs), *caches)
        return x, stacked, aux_total

    if mode == "train":
        x, auxes = jax.lax.scan(outer_train, x, seg_params)
        return x, None, aux_total + jnp.sum(auxes)
    x, (caches, auxes) = jax.lax.scan(outer_cached, x,
                                      (seg_params, seg_cache))
    return x, caches, aux_total + jnp.sum(auxes)


def _run_plan(plan, params_list, x, cfg, positions, mode, cache_list, memory,
              tables=None):
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, seg in enumerate(plan):
        c = cache_list[i] if cache_list is not None else None
        x, nc, a = _run_segment(params_list[i], seg, x, cfg, positions,
                                mode, c, memory, tables)
        new_caches.append(nc)
        aux += a
    return x, (new_caches if mode != "train" else None), aux


# ---------------------------------------------------------------------------
# Positions / embeddings
# ---------------------------------------------------------------------------


def default_positions(cfg: ModelConfig, b: int, s: int,
                      offset=None) -> jax.Array:
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (b, s))
    if offset is not None:
        pos = pos + offset[:, None]
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (b, s, len(cfg.mrope_sections)))
    return pos


def _embed_inputs(params, cfg, tokens, vision_embeds):
    x = embed(params["embed"], tokens)
    if vision_embeds is not None:
        # VLM stub: precomputed patch embeddings occupy the first P slots
        p = vision_embeds.shape[1]
        x = jax.lax.dynamic_update_slice_in_dim(
            x, vision_embeds.astype(x.dtype), 0, axis=1)
    return x


def _logits(params, cfg, x):
    if cfg.tie_embeddings:
        return unembed_tied(params["embed"], x, cfg.logit_softcap)
    return unembed(params["unembed"], x, cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Encoder (enc-dec models; frontend stub provides frame embeddings)
# ---------------------------------------------------------------------------


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    b, t, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x, _, _ = _run_plan(cfg.enc_plan(), params["encoder"], frames, cfg,
                        pos, "train", None, None)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Train / forward
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            positions=None, vision_embeds=None, frames=None):
    """Full-sequence forward -> (logits (B,S,V), aux)."""
    b, s = tokens.shape
    memory = encode(params, cfg, frames) if cfg.is_encdec else None
    if positions is None:
        positions = default_positions(cfg, b, s)
    x = _embed_inputs(params, cfg, tokens, vision_embeds)
    x, _, aux = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                          "train", None, memory)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), aux


AUX_WEIGHT = 0.01


def hidden_states(params, cfg: ModelConfig, tokens: jax.Array, *,
                  positions=None, vision_embeds=None, frames=None):
    """Trunk only: embeddings → stack → final norm.  (B, S, D)."""
    b, s = tokens.shape
    memory = encode(params, cfg, frames) if cfg.is_encdec else None
    if positions is None:
        positions = default_positions(cfg, b, s)
    x = _embed_inputs(params, cfg, tokens, vision_embeds)
    x, _, aux = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                          "train", None, memory)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def loss_fn(params, cfg: ModelConfig, batch: dict):
    """batch: tokens (B,S), labels (B,S) (-1 = pad), optional positions /
    vision_embeds / frames.  Returns (loss, metrics).

    The unembed+xent runs seq-chunked (cfg.loss_chunk) so the full
    (B, S, V) logits tensor never exists — for 128k-256k vocab configs
    this is the dominant activation saving of the whole step."""
    x, aux = hidden_states(
        params, cfg, batch["tokens"],
        positions=batch.get("positions"),
        vision_embeds=batch.get("vision_embeds"),
        frames=batch.get("frames"))
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    xent = chunked_unembed_xent(lambda xc: _logits(params, cfg, xc),
                                x, jnp.maximum(labels, 0), mask,
                                cfg.loss_chunk)
    loss = xent + AUX_WEIGHT * aux
    return loss, {"loss": loss, "xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Cache + serving entry points
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_context: int,
               enc_len: int = 0, layout: str = "ring",
               num_pages: int = 0, page_size: int = 128) -> dict:
    """Decode-state pytree.  ``layout='ring'`` (default) builds the
    slot-contiguous ring buffers; ``layout='paged'`` builds one shared
    page pool per layer sized by the allocator's ``num_pages`` — block
    tables (traced per step) then map each slot onto its pages, so
    admission/eviction never changes the compiled shapes."""
    dtype = jnp.dtype(cfg.dtype)
    if layout == "paged" and num_pages <= 0:
        raise ValueError("paged cache layout needs num_pages > 0")

    def seg_cache(seg: Segment):
        out = {}
        for j, (spec, n) in enumerate(seg.pattern):
            if layout == "paged":
                one = init_paged_block_cache(cfg, spec, num_pages,
                                             page_size, dtype)
            else:
                one = init_block_cache(cfg, spec, batch, max_context, dtype,
                                       enc_len)
            dims = (seg.repeat, n) if seg.repeat > 1 else (n,)
            out[f"e{j}"] = jax.tree.map(
                lambda a: jnp.tile(a, dims + (1,) * a.ndim), one)
        return out

    return {
        "segments": [seg_cache(s) for s in cfg.plan()],
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params, cfg: ModelConfig, tokens: jax.Array, cache: dict, *,
            positions=None, vision_embeds=None, frames=None):
    """One-shot prefill from position 0.  Returns (last-token logits (B,V),
    updated cache)."""
    b, s = tokens.shape
    memory = encode(params, cfg, frames) if cfg.is_encdec else None
    if positions is None:
        positions = default_positions(cfg, b, s)
    x = _embed_inputs(params, cfg, tokens, vision_embeds)
    x, segs, _ = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                           "prefill", cache["segments"], memory)
    x_last = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = _logits(params, cfg, x_last)[:, 0]
    new_cache = {"segments": segs,
                 "pos": jnp.full((b,), s, jnp.int32)}
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, cache: dict,
                tables=None):
    """tokens: (B, 1) — one new token per sequence.  ``tables`` (B, P)
    carries the live allocator block tables for a paged-layout cache
    (traced, so page churn never recompiles).  Returns
    (logits (B,V), updated cache)."""
    b = tokens.shape[0]
    pos = cache["pos"]                                   # (B,)
    positions = pos[:, None]
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[..., None],
                                     (b, 1, len(cfg.mrope_sections)))
    x = embed(params["embed"], tokens)
    x, segs, _ = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                           "step", cache["segments"], None, tables)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)[:, 0]
    return logits, {"segments": segs, "pos": pos + 1}


def prefill_paged(params, cfg: ModelConfig, tokens: jax.Array, cache: dict,
                  tables: jax.Array, start: jax.Array, slot: jax.Array):
    """Suffix prefill into the shared page pool.

    tokens (1, S): the *uncached* prompt suffix; start (1,) int32: the
    cached-prefix length (absolute position of tokens[0]); tables (1, P):
    the sequence's block-table row (prefix pages first — already holding
    a sibling's KV — then private pages); slot: the batch slot whose
    ``pos`` to advance.  The cached prefix is never recomputed and never
    copied: its pages are simply referenced by id, which is the
    zero-copy shared-prefix admission path.  Returns (last-token logits
    (1, V), updated cache)."""
    b, s = tokens.shape
    positions = default_positions(cfg, b, s, offset=start)
    x = _embed_inputs(params, cfg, tokens, None)
    x, segs, _ = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                           "prefill", cache["segments"], None, tables)
    x_last = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = _logits(params, cfg, x_last)[:, 0]
    pos = cache["pos"].at[slot].set(start[0] + s)
    return logits, {"segments": segs, "pos": pos}


def prefill_paged_padded(params, cfg: ModelConfig, tokens: jax.Array,
                         cache: dict, tables: jax.Array, start: jax.Array,
                         slot: jax.Array, n: jax.Array):
    """Shape-stable suffix prefill: a fixed-capacity chunk buffer with a
    *traced* valid length.

    tokens (1, C): prompt-chunk buffer at fixed capacity C; only the
    first ``n`` tokens are real (``n`` is a traced int32, so varying
    chunk fill never retraces — the mixed step's contract).  Padded tail
    positions are -1: their KV writes route to the pool's sink page
    (``_phys_slots``) and their queries mask to nothing, so the padding
    is inert.  Logits are taken at index ``n - 1`` (the last *valid*
    token) and ``pos[slot]`` advances to ``start + n``.  ``start`` and
    ``slot`` are scalar traced int32."""
    b, c = tokens.shape
    idx = jnp.arange(c, dtype=jnp.int32)[None, :]
    positions = jnp.where(idx < n, start + idx, -1)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[..., None],
                                     (b, c, len(cfg.mrope_sections)))
    x = _embed_inputs(params, cfg, tokens, None)
    x, segs, _ = _run_plan(cfg.plan(), params["decoder"], x, cfg, positions,
                           "prefill", cache["segments"], None, tables)
    x_last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
    x_last = rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
    logits = _logits(params, cfg, x_last)[:, 0]
    pos = cache["pos"].at[slot].set(start + n)
    return logits, {"segments": segs, "pos": pos}


# ---------------------------------------------------------------------------
# Paged <-> ring state bridge (KV migration for paged engines)
# ---------------------------------------------------------------------------


def _map_paged_kv(cache: dict, fn):
    """Apply ``fn(paged_kv, stack_dims)`` to every PagedKVCache in the
    cache's segment tree (leaves carry leading layer-stack dims)."""
    from repro.models.attention import PagedKVCache

    def walk(node):
        if isinstance(node, PagedKVCache):
            return fn(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(cache["segments"])


def paged_extract(cfg: ModelConfig, cache: dict, table_row, ctx: int,
                  max_context: int, slot: int) -> dict:
    """Pull one sequence out of the paged pool as a batch-1 *ring*-layout
    cache — the same format ring engines extract/inject, so KV migration
    is layout-agnostic (a paged engine can hand a sequence to a ring
    engine and vice versa)."""
    from repro.models import attention as attn

    tables = jnp.asarray(table_row, jnp.int32)[None]       # (1, P)

    def one(pkv):
        def leaf(k, v):
            # collapse layer-stack dims, gather per layer, re-stack
            stack = k.shape[:-4]
            kf = k.reshape((-1,) + k.shape[-4:])
            vf = v.reshape((-1,) + v.shape[-4:])
            outs = [_pool_to_ring(cfg, attn.PagedKVCache(kf[i], vf[i]),
                                  tables, ctx, max_context)
                    for i in range(kf.shape[0])]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            return jax.tree.map(
                lambda a: a.reshape(stack + a.shape[1:]), stacked)
        return leaf(pkv.k, pkv.v)

    segs = _map_paged_kv(cache, one)
    pos = jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1)
    return {"segments": segs, "pos": pos}


def _pool_to_ring(cfg, pkv, tables, ctx: int, max_context: int):
    from repro.models import attention as attn
    view = attn.paged_view(pkv, tables)                  # (1, P*page, ...)
    size = max_context
    ring = attn.init_kv_cache(1, size, pkv.k.shape[-3], pkv.k.shape[-2],
                              pkv.k.dtype)
    if ctx <= 0:
        return ring
    n = min(ctx, view.k.shape[1])
    return attn.cache_write(ring, view.k[:, :n], view.v[:, :n],
                            jnp.zeros((1,), jnp.int32))


def paged_insert(cfg: ModelConfig, cache: dict, sub: dict, table_row,
                 slot) -> dict:
    """Install a batch-1 ring-layout cache (from ``paged_extract`` or a
    ring engine's extract) into the paged pool at ``table_row``'s pages.
    Ring slots are scattered through their absolute ``kpos`` (wrapped
    SWA rings land at the right logical pages; empty slots hit the
    sink)."""
    from repro.models import attention as attn

    tables = jnp.asarray(table_row, jnp.int32)[None]       # (1, P)
    sub_leaves = []

    def collect(node):
        if isinstance(node, attn.KVCache):
            sub_leaves.append(node)
            return node
        if isinstance(node, dict):
            for v in node.values():
                collect(v)
        elif isinstance(node, list):
            for v in node:
                collect(v)
        return node

    collect(sub["segments"])
    it = iter(sub_leaves)

    def one(pkv):
        ring = next(it)

        def leaf(k, v, rk, rv, rpos):
            stack = k.shape[:-4]
            kf = k.reshape((-1,) + k.shape[-4:])
            vf = v.reshape((-1,) + v.shape[-4:])
            rkf = rk.reshape((-1,) + rk.shape[-4:])
            rvf = rv.reshape((-1,) + rv.shape[-4:])
            rpf = rpos.reshape((-1,) + rpos.shape[-2:])
            outs = [attn.paged_cache_write_at(
                        attn.PagedKVCache(kf[i], vf[i]),
                        rkf[i].astype(kf.dtype), rvf[i].astype(kf.dtype),
                        rpf[i], tables)
                    for i in range(kf.shape[0])]
            ks = jnp.stack([o.k for o in outs]).reshape(stack + k.shape[-4:])
            vs = jnp.stack([o.v for o in outs]).reshape(stack + v.shape[-4:])
            return attn.PagedKVCache(ks, vs)
        return leaf(pkv.k, pkv.v, ring.k, ring.v, ring.kpos)

    segs = _map_paged_kv(cache, one)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], sub["pos"].astype(jnp.int32), slot, axis=0)
    return {"segments": segs, "pos": pos}
