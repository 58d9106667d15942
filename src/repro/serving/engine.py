"""Real JAX engine: actual forward passes with slot-batched KV caches.

The decode hot path is ONE fixed-shape jitted step over all slots
(continuous batching, TPU-style: inactive slots ride along as padding so
the compiled executable never changes shape).  Sampling is **fused into
the step**: the jitted function runs forward pass → logits →
greedy/temperature sample and returns int32 token ids, so the (B, V)
logits never leave the device and the only host transfer per step is
the sampled tokens themselves.

Two KV layouts, selected by the ``cache_layout`` knob:

* ``ring``  — the classic slot-contiguous ring buffers.  Prefill always
  recomputes the full prompt into a fresh batch-1 sub-cache, then the
  slice is inserted into the batched cache (serving/cache_utils).
* ``paged`` — one shared page pool per layer, sized by the scheduler's
  ``PageAllocator`` (pool page *i* IS allocator page *i*).  The jitted
  decode step takes the live block tables as a **traced** ``(slots,
  P_max) int32`` input, so admission/eviction/preemption churn never
  recompiles, and decode attention runs ``ops.paged_decode_attention``
  straight over allocator state when ``cfg.use_pallas`` is set.
  Prefill computes only the *uncached suffix* of the prompt: a shared
  prefix acquired from the prefix cache is just page ids in the block
  table — zero KV copies at admission.

Serialized prefill runs per request at its exact suffix length (CPU
container: a handful of lengths per test/example; on TPU you'd bucket).
With the scheduler's ``mixed`` knob on (paged layout only), prefill
stops serializing against decode entirely: one jitted ``_mixed_step``
co-runs every live decode slot with one padded prefill chunk —
fixed-capacity chunk buffer, traced valid length, traced block tables —
so the executable compiles exactly once per engine and a long prompt
never stalls the decode batch.  Slot state surgery uses
serving/cache_utils (ring) or the transformer's
paged_extract/paged_insert bridge (paged); KV migration uses
serving/kv_transfer in both layouts.

Observability: with an enabled ``core.trace.Tracer`` attached
(``attach_tracer``), every ``step()`` records an ``engine.step`` span
whose children tile it — ``engine.plan`` (the scheduler),
``engine.inputs`` (block tables, token buffers, the key split and their
host-to-device copies), ``engine.dispatch`` (the jitted call until it
returns), ``engine.sync`` (the device-to-host read of the sampled
tokens) and ``engine.apply`` (bookkeeping) — each also a profiler
``TraceAnnotation``.  ``traces`` counts each jitted function's traces
(a compile or a compile-cache load).
"""
from __future__ import annotations

import time
from collections import Counter
from functools import wraps

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig
from repro.core.knobs import KnobSpec
from repro.core.trace import NO_PHASE
from repro.core.types import Request, RequestState
from repro.serving import cache_utils, sampler
from repro.serving.engine_base import EngineCore
from repro.serving.kv_cache import block_tables
from repro.serving.scheduler import SchedulerConfig, StepKind


class Engine(EngineCore):
    KNOB_SPECS = EngineCore.KNOB_SPECS + (
        KnobSpec("cache_layout", kind="str", choices=("ring", "paged"),
                 attr="_cache_layout", on_change="_cache_layout_changed",
                 doc="KV cache layout: 'ring' slot-contiguous buffers or "
                     "'paged' shared page pool driven by live allocator "
                     "block tables (the Pallas fast path)"),
    )

    def __init__(self, cfg: ModelConfig, params, sched_cfg: SchedulerConfig,
                 name: str = "engine", collector=None, seed: int = 0,
                 cache_layout: str | None = None):
        sched_cfg.require_complete_prompt = True   # one-shot real prefill
        super().__init__(name, cfg.name, sched_cfg, collector)
        self.cfg = cfg
        self.params = params
        self._t0 = time.monotonic()
        self._key = jax.random.key(seed)
        self._axes = cache_utils.batch_axes(cfg, sched_cfg.max_context)
        # fixed block-table width: the allocator can never hand a live
        # sequence more pages than a max_context footprint
        self._p_max = self.scheduler.alloc.pages_for(sched_cfg.max_context)
        if cache_layout is None:
            cache_layout = "paged" if cfg.use_pallas else "ring"
        self._cache_layout = cache_layout
        self._last_token = np.zeros((sched_cfg.max_slots,), np.int32)
        self._build_cache()
        # traces of each jitted function, by name: a Python side effect
        # of the traced body runs per TRACE, not per call
        self.traces: Counter = Counter()

        def jit(donate):
            def wrap(fn):
                @wraps(fn)
                def counted(*args):
                    self.traces[fn.__name__] += 1
                    return fn(*args)
                return jax.jit(counted, donate_argnums=donate)
            return wrap

        # every step consumes the previous cache and returns the next one,
        # so the cache buffers are donated: the in-place update XLA can do
        # then is what makes the shared page pool (one big buffer per
        # layer, scatter-written every step) cost the same as the ring
        @jit(donate=(2,))
        def _prefill(params, tokens, cache, key, temperature):
            # forward + first-token sample in one program: logits are
            # consumed on-device, only the token id comes back
            logits, cache = models.prefill(params, cfg, tokens, cache)
            tok = sampler.sample(logits, key, temperature)
            return tok, cache

        @jit(donate=(2,))
        def _decode(params, tokens, cache, key, temperature):
            logits, cache = models.decode_step(params, cfg, tokens, cache)
            tok = sampler.sample(logits, key, temperature)
            return tok, cache

        @jit(donate=(2,))
        def _prefill_paged(params, tokens, cache, tables, start, slot, key,
                           temperature):
            logits, cache = models.prefill_paged(params, cfg, tokens, cache,
                                                 tables, start, slot)
            tok = sampler.sample(logits, key, temperature)
            return tok, cache

        @jit(donate=(2,))
        def _decode_paged(params, tokens, cache, tables, key, temperature):
            logits, cache = models.decode_step(params, cfg, tokens, cache,
                                               tables)
            tok = sampler.sample(logits, key, temperature)
            return tok, cache

        # stall-free mixed step: ALL decode slots + one padded prefill
        # chunk in a single jitted program.  Every input is shape-stable
        # (fixed slot count, fixed chunk capacity, fixed-width block
        # tables; start/valid-length/slot are traced scalars), so the
        # executable compiles exactly once per engine — allocator churn,
        # admission, and varying chunk fill never retrace.  The decode
        # sub-forward runs first (its writes land in the decode
        # sequences' own pages); the prefill chunk then attends into its
        # resident prefix pages and sets its slot's pos absolutely,
        # overwriting the blanket pos+1 the decode bookkeeping applied.
        @jit(donate=(3,))
        def _mixed_step(params, dec_tokens, pf_tokens, cache, dec_tables,
                        pf_tables, pf_start, pf_n, pf_slot, key, temperature):
            dec_logits, cache = models.decode_step(params, cfg, dec_tokens,
                                                   cache, dec_tables)
            pf_logits, cache = models.prefill_paged_padded(
                params, cfg, pf_tokens, cache, pf_tables, pf_start, pf_slot,
                pf_n)
            kd, kp = jax.random.split(key)
            dec_tok = sampler.sample(dec_logits, kd, temperature)
            pf_tok = sampler.sample(pf_logits, kp, temperature)
            return dec_tok, pf_tok, cache

        @jit(donate=(0,))
        def _insert(cache, sub, slot):
            return cache_utils.cache_insert(cache, sub, slot, self._axes)

        @jit(donate=())
        def _extract(cache, slot):
            return cache_utils.cache_extract(cache, slot, self._axes)

        self._prefill_fn = _prefill
        self._decode_fn = _decode
        self._prefill_paged_fn = _prefill_paged
        self._decode_paged_fn = _decode_paged
        self._mixed_fn = _mixed_step
        self._insert_fn = _insert
        self._extract_fn = _extract
        # fixed chunk-buffer capacity for the mixed step, set once at
        # construction so retuning the prefill_chunk knob never changes
        # the compiled shape (knob values above the cap are clamped)
        self._mixed_cap = min(sched_cfg.max_batch_tokens,
                              sched_cfg.max_context)
        if sched_cfg.mixed and self._cache_layout != "paged":
            raise RuntimeError(
                f"{name}: mixed batching needs the paged cache layout "
                f"(got {self._cache_layout!r})")

    @property
    def mixed_step_traces(self) -> int:
        """Traces of the jitted mixed step: the compile-once gate."""
        return self.traces["_mixed_step"]

    # ----------------------------------------------------------- cache layout
    @property
    def cache_layout(self) -> str:
        return self._cache_layout

    def _build_cache(self) -> None:
        sc = self.scheduler.cfg
        if self._cache_layout == "paged":
            self.cache = models.init_cache(
                self.cfg, sc.max_slots, sc.max_context, layout="paged",
                num_pages=sc.num_pages, page_size=sc.page_size)
        else:
            self.cache = models.init_cache(self.cfg, sc.max_slots,
                                           sc.max_context)

    def _cache_layout_changed(self, old: str, new: str) -> None:
        if old == new:
            return
        if self.scheduler.num_running > 0:
            self._cache_layout = old            # revert before failing
            raise RuntimeError(
                f"{self.name}: cache_layout flip needs an idle engine "
                f"({self.scheduler.num_running} sequences running)")
        if new == "ring" and self.scheduler.cfg.mixed:
            self._cache_layout = old            # revert before failing
            raise RuntimeError(
                f"{self.name}: cache_layout 'ring' is incompatible with "
                "mixed batching — set mixed false first")
        self._build_cache()

    def on_knob_set(self, name: str, old, new) -> None:
        if name == "mixed" and new and self._cache_layout != "paged":
            self.scheduler.cfg.mixed = old      # revert before failing
            raise RuntimeError(
                f"{self.name}: mixed batching needs the paged cache "
                f"layout (current: {self._cache_layout!r})")
        super().on_knob_set(name, old, new)

    def _block_table_rows(self, reqs: list[Request]) -> np.ndarray:
        """(max_slots, P_max) int32 table for the jitted step: live rows
        come straight from ``PageAllocator.page_table`` (physical ids in
        logical order); inactive slots are all -1 (their writes land in
        the pool's sink page, their reads mask out)."""
        slots = self.scheduler.cfg.max_slots
        out = np.full((slots, self._p_max), -1, np.int32)
        live = [r for r in reqs if 0 <= r.slot < slots]
        if live:
            rows = block_tables(self.scheduler.alloc,
                                [r.req_id for r in live], width=self._p_max)
            for r, row in zip(live, rows):
                out[r.slot] = row
        return out

    # ------------------------------------------------------------------ time
    def now(self) -> float:
        return time.monotonic() - self._t0

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # --------------------------------------------------------------- tracing
    def attach_tracer(self, tracer):
        """Attach ``tracer`` (built on this engine's ``now``) and anchor
        its clock to the host's, so its spans line up with a profile."""
        tracer.anchor(f"{self.name}.now")
        return super().attach_tracer(tracer)

    def _phase(self, name: str, parent=None):
        tr = self.tracer
        if tr is None:
            return NO_PHASE
        return tr.phase(name, parent, engine=self.name)

    # ------------------------------------------------------------------ step
    def step(self) -> StepKind:
        """Run one scheduler plan synchronously.  Returns the plan kind.

        Traced, the ``engine.step`` span carries ``kind``, ``rows``
        (decode rows advanced), ``chunk`` (prefill tokens computed) and
        ``traced`` (a jitted function was traced in this step)."""
        if self.paused:
            return StepKind.IDLE
        t_start = time.monotonic()
        with self._phase("engine.step") as root:
            with self._phase("engine.plan", root):
                plan = self.scheduler.plan_step()
                live = ([r for r in plan.decodes
                         if self.scheduler.ensure_decode_capacity(r)]
                        if plan.kind in (StepKind.DECODE, StepKind.MIXED)
                        else [])
            n_traces = sum(self.traces.values()) if root is not None else 0
            if plan.kind == StepKind.PREFILL:
                firsts = [self._run_prefill_work(work, root)
                          for work in plan.prefills]
            elif plan.kind == StepKind.MIXED:
                dec_tok, first = self._run_mixed(plan.prefills[0], live, root)
            elif plan.kind == StepKind.DECODE and live:
                dec_tok = self._run_decode(live, root)
            with self._phase("engine.apply", root):
                if plan.kind == StepKind.PREFILL:
                    self.apply_prefill(plan.prefills, firsts, self.now())
                elif plan.kind == StepKind.MIXED:
                    toks = self._take_tokens(live, dec_tok)
                    now = self.now()
                    self.apply_prefill(plan.prefills[:1], [first], now)
                    if live:
                        self.apply_decode(live, toks, now)
                elif plan.kind == StepKind.DECODE and live:
                    toks = self._take_tokens(live, dec_tok)
                    self.apply_decode(live, toks, self.now())
                self.steps += 1
                self._step_metrics(time.monotonic() - t_start)
            if root is not None:
                root.attrs.update(
                    kind=plan.kind.value, rows=len(live),
                    chunk=sum(w.chunk for w in plan.prefills),
                    traced=sum(self.traces.values()) > n_traces)
        return plan.kind

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.busy:
                break
            self.step()

    def _take_tokens(self, reqs: list[Request], toks: np.ndarray
                     ) -> list[int]:
        """Each row's sampled token, kept as its next decode input."""
        out = []
        for r in reqs:
            t = int(toks[r.slot])
            self._last_token[r.slot] = t
            out.append(t)
        return out

    # ---------------------------------------------------------------- prefill
    def _run_prefill_work(self, work, root=None):
        if self._cache_layout == "paged":
            # the scheduler's chunk is honored as planned: a chunked
            # prefill spans multiple steps (the prefill_chunk knob is
            # live on real hardware, not just in the sim)
            return self._run_prefill_paged(work.req, work.chunk, root)
        work.chunk = work.req.prompt_len        # ring: one shot
        return self._run_prefill(work.req, root)

    def _run_prefill(self, req: Request, root=None) -> int:
        with self._phase("engine.inputs", root):
            tokens = jnp.asarray(req.prompt_tokens, jnp.int32)[None, :]
            sub_cache = models.init_cache(self.cfg, 1,
                                          self.scheduler.cfg.max_context)
            key, temp = self._next_key(), jnp.float32(self.temperature)
        with self._phase("engine.dispatch", root):
            tok, sub_cache = self._prefill_fn(self.params, tokens, sub_cache,
                                              key, temp)
            self.cache = self._insert_fn(self.cache, sub_cache,
                                         jnp.int32(req.slot))
        with self._phase("engine.sync", root):
            first = int(tok[0])
        self._last_token[req.slot] = first
        return first

    def _run_prefill_paged(self, req: Request, chunk: int, root=None):
        """Prefill ``chunk`` uncached prompt tokens straight into the
        shared pool, honoring the scheduler's chunked-prefill plan.

        ``req.prefilled`` tokens of prompt are already resident (shared
        prefix pages acquired by id at admission — never copied, or
        earlier chunks of this same prefill); the block-table row lays
        those pages first, so the chunk attends back into resident KV
        through the ordinary paged gather.  Returns the sampled first
        token when this chunk completes the prompt, else None (the
        request keeps prefilling next step)."""
        start = min(req.prefilled, req.prompt_len - 1)
        chunk = min(chunk, req.prompt_len - start)
        with self._phase("engine.inputs", root):
            tokens = jnp.asarray(req.prompt_tokens[start:start + chunk],
                                 jnp.int32)[None, :]
            row = self._block_table_rows([req])[req.slot][None, :]
            args = (jnp.asarray(row), jnp.full((1,), start, jnp.int32),
                    jnp.int32(req.slot), self._next_key(),
                    jnp.float32(self.temperature))
        with self._phase("engine.dispatch", root):
            tok, self.cache = self._prefill_paged_fn(
                self.params, tokens, self.cache, *args)
        if start + chunk < req.prompt_len:
            return None                     # chunk not final: no token yet
        with self._phase("engine.sync", root):
            first = int(tok[0])
        self._last_token[req.slot] = first
        return first

    # ------------------------------------------------------------------ mixed
    def _run_mixed(self, work, live: list[Request], root=None):
        """One fused step: every live decode slot advances a token while
        one prefill chunk computes — the stall-free continuous-batching
        hot path.  All jit inputs are shape-stable; see ``_mixed_step``
        in ``__init__``.  Returns (the decode rows' tokens by slot, the
        chunk's first token or None)."""
        if self._cache_layout != "paged":
            raise RuntimeError(
                f"{self.name}: mixed batching needs the paged cache "
                f"layout (current: {self._cache_layout!r})")
        req = work.req
        cap = self._mixed_cap
        chunk = min(work.chunk, cap)
        work.chunk = chunk                  # bookkeeping sees the clamp
        start = req.prefilled
        with self._phase("engine.inputs", root):
            buf = np.zeros((1, cap), np.int32)
            buf[0, :chunk] = req.prompt_tokens[start:start + chunk]
            dec_tables = self._block_table_rows(live)
            pf_row = self._block_table_rows([req])[req.slot][None, :]
            args = (jnp.asarray(self._last_token[:, None]), jnp.asarray(buf),
                    self.cache, jnp.asarray(dec_tables), jnp.asarray(pf_row),
                    jnp.int32(start), jnp.int32(chunk), jnp.int32(req.slot),
                    self._next_key(), jnp.float32(self.temperature))
        with self._phase("engine.dispatch", root):
            dec_tok, pf_tok, self.cache = self._mixed_fn(self.params, *args)
        final = (start + chunk) >= req.prompt_len
        with self._phase("engine.sync", root):
            dec_tok = np.asarray(dec_tok)
            first = int(pf_tok[0]) if final else None
        if final:
            self._last_token[req.slot] = first
        return dec_tok, first

    # ----------------------------------------------------------------- decode
    def _run_decode(self, reqs: list[Request], root=None) -> np.ndarray:
        """One decode step over every slot; the sampled tokens by slot."""
        with self._phase("engine.inputs", root):
            tokens = jnp.asarray(self._last_token[:, None])
            tables = (jnp.asarray(self._block_table_rows(reqs))
                      if self._cache_layout == "paged" else None)
            key, temp = self._next_key(), jnp.float32(self.temperature)
        with self._phase("engine.dispatch", root):
            if tables is not None:
                toks, self.cache = self._decode_paged_fn(
                    self.params, tokens, self.cache, tables, key, temp)
            else:
                toks, self.cache = self._decode_fn(
                    self.params, tokens, self.cache, key, temp)
        with self._phase("engine.sync", root):
            return np.asarray(toks)

    # ------------------------------------------------------------ kv transfer
    def extract_state(self, req: Request):
        """(cache-slice pytree, last_token, nbytes) for migration.  Both
        layouts export the same batch-1 ring-format pytree, so the
        transfer plane and the receiving engine never care which layout
        produced it."""
        if self._cache_layout == "paged":
            row = self._block_table_rows([req])[req.slot]
            ctx = int(jax.device_get(self.cache["pos"])[req.slot])
            sub = models.paged_extract(self.cfg, self.cache, row, ctx,
                                       self.scheduler.cfg.max_context,
                                       req.slot)
        else:
            sub = self._extract_fn(self.cache, jnp.int32(req.slot))
        return {"cache": jax.device_get(sub),
                "last_token": int(self._last_token[req.slot]),
                "nbytes": cache_utils.cache_nbytes(sub)}

    def inject_state(self, req: Request, state: dict) -> None:
        """Install a migrated request into a fresh slot (already admitted:
        req.slot assigned, scheduler pages reserved)."""
        if self._cache_layout == "paged":
            row = self._block_table_rows([req])[req.slot]
            self.cache = models.paged_insert(self.cfg, self.cache,
                                             state["cache"], row,
                                             req.slot)
        else:
            self.cache = self._insert_fn(self.cache, state["cache"],
                                         jnp.int32(req.slot))
        self._last_token[req.slot] = state["last_token"]
        req.state = RequestState.RUNNING
        req.prefilled = req.prompt_len
