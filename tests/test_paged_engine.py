"""Live-engine paged-vs-ring equivalence + allocator churn.

The paged-pool KV layout (models/attention.py PagedKVCache) must be
token-exact with the ring-buffer oracle when the real Engine drives it
through live PageAllocator block tables — across GQA/MQA, sliding
windows, non-page-aligned contexts, the Pallas kernel path
(interpret mode on CPU), shared-prefix admission, preemption churn and
KV migration.  These are the CI gates for the measured fast path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core.metrics import BUILTIN_SPECS, Collector, MetricBus
from repro.core.types import Request, RequestState
from repro.models import transformer as tfm
from repro.serving.engine import Engine
from repro.serving.kv_cache import PageAllocator, block_tables
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import SchedulerConfig


BASE = get_config("tiny-agent").replace(dtype="float32")
PAGE = 16


def _params(cfg):
    return models.init(cfg, jax.random.key(0))


def _engine(cfg, params, layout, num_pages=64, max_slots=2, cache=False,
            name=None):
    sched = SchedulerConfig(max_slots=max_slots, num_pages=num_pages,
                            max_context=128, page_size=PAGE)
    name = name or f"pe-{layout}"
    eng = Engine(cfg, params, sched, name=name, cache_layout=layout)
    if cache:
        pc = PrefixCache(eng.scheduler.alloc, name=f"{name}.cache",
                         instance=name, block_tokens=PAGE, reserve_frac=0.8)
        eng.attach_cache(pc)
    return eng


def _run(eng, prompts, max_new=6):
    reqs = [Request(prompt_len=len(p), max_new_tokens=max_new,
                    prompt_tokens=np.asarray(p, np.int32)) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        assert r.state == RequestState.FINISHED
    return [r.output_tokens for r in reqs]


# ---------------------------------------------------------------------------
# Model-level parity: ring oracle vs paged gather vs Pallas kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_kv_heads", [2, 1], ids=["gqa", "mqa"])
@pytest.mark.parametrize("window", [-1, 24], ids=["full", "swa"])
def test_paged_model_logit_parity(n_kv_heads, window):
    """Non-page-aligned prompt, decode tail crossing a page boundary."""
    cfg = BASE.replace(n_kv_heads=n_kv_heads, window=window)
    params = _params(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 27), 0, cfg.vocab)

    ring = models.init_cache(cfg, 2, 96)
    lr, ring = models.prefill(params, cfg, toks, ring)

    paged = models.init_cache(cfg, 2, 96, layout="paged", num_pages=16,
                              page_size=PAGE)
    pmax = 96 // PAGE
    tables = jnp.asarray([[b * pmax + j for j in range(pmax)]
                          for b in range(2)], jnp.int32)
    lps = []
    for b in range(2):
        lp, paged = tfm.prefill_paged(params, cfg, toks[b:b + 1], paged,
                                      tables[b:b + 1],
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.int32(b))
        lps.append(lp)
    lp = jnp.concatenate(lps)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                               rtol=1e-4, atol=1e-4)

    cfgk = cfg.replace(use_pallas=True)
    tok_r = jnp.argmax(lr, -1)[:, None]
    tok_p = tok_r
    for _ in range(8):                 # crosses the 27->32 page boundary
        lr, ring = models.decode_step(params, cfg, tok_r, ring)
        lp, paged = models.decode_step(params, cfgk, tok_p, paged, tables)
        # kernel accumulates in a different order (lane padding + scale
        # compensation): logits agree loosely, argmax tokens exactly
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                                   rtol=2e-3, atol=2e-3)
        tok_r = jnp.argmax(lr, -1)[:, None]
        tok_p = jnp.argmax(lp, -1)[:, None]
        assert (tok_r == tok_p).all()


# ---------------------------------------------------------------------------
# Live-engine token parity (the acceptance gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_kv_heads", [2, 1], ids=["gqa", "mqa"])
@pytest.mark.parametrize("window", [-1, 24], ids=["full", "swa"])
def test_live_engine_paged_vs_ring_tokens(n_kv_heads, window):
    cfg = BASE.replace(n_kv_heads=n_kv_heads, window=window)
    params = _params(cfg)
    prompts = [np.arange(5, 40) % cfg.vocab,      # 35: non-aligned
               np.arange(3, 30) % cfg.vocab]      # 27: < 2 pages
    ring = _run(_engine(cfg, params, "ring"), prompts)
    paged = _run(_engine(cfg, params, "paged"), prompts)
    kernel = _run(_engine(cfg.replace(use_pallas=True), params, "paged"),
                  prompts)
    assert ring == paged == kernel


def test_live_engine_executes_pallas_kernel(monkeypatch):
    """The acceptance criterion literally: Engine decode calls
    ops.paged_decode_attention with the allocator's live block table."""
    from repro.kernels import ops
    cfg = BASE.replace(use_pallas=True)
    params = _params(cfg)
    eng = _engine(cfg, params, "paged")
    calls = []
    real = ops.paged_decode_attention

    def spy(q, k_pages, v_pages, tables, ctx, **kw):
        # debug.callback delivers the *runtime* table values even though
        # the spy itself runs once at trace time inside the jitted step
        jax.debug.callback(lambda t: calls.append(np.asarray(t)), tables)
        return real(q, k_pages, v_pages, tables, ctx, **kw)

    monkeypatch.setattr(ops, "paged_decode_attention", spy)
    p = np.arange(4, 30) % cfg.vocab
    r = Request(prompt_len=len(p), max_new_tokens=3,
                prompt_tokens=np.asarray(p, np.int32))
    eng.submit(r)
    eng.step()                                   # prefill
    expect = eng.scheduler.alloc.page_table(r.req_id)
    eng.step()                                   # decode
    jax.effects_barrier()
    assert calls, "decode never reached the paged kernel"
    row = calls[-1][r.slot]
    assert list(row[:len(expect)]) == expect
    assert (row[len(expect):] == -1).all()


# ---------------------------------------------------------------------------
# Zero-copy shared prefixes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,seed", [(1, 0), (1, 1), (7, 2), (16, 3),
                                    (17, 4), (40, 5)])
def test_page_fill_matches_token_scatter(s, seed):
    """The engine's page-granular write (``paged_cache_fill``: gather
    whole pages, fill, scatter back) leaves every real page as the plain
    per-token scatter does: decode (S = 1) and chunks spanning pages,
    with unmapped table entries, positions past the table, -1 tails and
    an empty row."""
    from repro.models import attention as attn
    rng = np.random.default_rng(seed)
    b, n, p_max, hkv, dh = 4, 30, 5, 2, 3
    pool = attn.PagedKVCache(
        *(jnp.asarray(rng.normal(size=(n + 1, hkv, dh, PAGE)), jnp.float32)
          for _ in range(2)))
    tables = rng.permutation(n)[:b * p_max].reshape(b, p_max)
    tables = np.where(rng.random((b, p_max)) < 0.2, -1, tables)
    start = rng.integers(0, p_max * PAGE, size=b)
    start[0] = -1                                       # an empty row
    n_real = rng.integers(0, s + 1, size=b)
    n_real[1] = s
    pos = np.where(np.arange(s) < n_real[:, None],
                   start[:, None] + np.arange(s), -1)
    pos[start < 0] = -1
    k_new = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)
    args = (k_new, -k_new, jnp.asarray(pos, jnp.int32),
            jnp.asarray(tables, jnp.int32))
    got = attn.paged_cache_fill(pool, *args)
    want = attn.paged_cache_write_at(pool, *args)
    for g, w in zip(got, want):                 # the sink page may differ
        np.testing.assert_array_equal(np.asarray(g[:n]), np.asarray(w[:n]))


def test_shared_prefix_zero_copy_admission():
    cfg = BASE.replace(use_pallas=True)
    params = _params(cfg)
    eng = _engine(cfg, params, "paged", cache=True)
    shared = (np.arange(11, 43) % cfg.vocab).astype(np.int32)   # 2 pages
    pA = np.concatenate([shared, np.asarray([7, 8, 9], np.int32)])
    pB = np.concatenate([shared, np.asarray([1, 2, 3, 4], np.int32)])

    rA = Request(prompt_len=len(pA), max_new_tokens=5, prompt_tokens=pA)
    eng.submit(rA)
    eng.run_until_idle()
    prefix_ids = eng.scheduler.cache.chain(list(shared))
    shared_pages = [pid for blk in prefix_ids
                    for pid in eng.scheduler.alloc.block_pages(blk.digest)]
    assert len(shared_pages) == len(shared) // PAGE

    rB = Request(prompt_len=len(pB), max_new_tokens=5, prompt_tokens=pB)
    eng.submit(rB)
    eng.step()                     # admit + suffix prefill
    # the cached prefix was acquired by PHYSICAL ID — rB's table starts
    # with the exact pages rA's prefill wrote; nothing was copied
    assert rB.meta["cached_prompt_tokens"] == len(shared)
    assert eng.scheduler.alloc.page_table(rB.req_id)[:len(shared_pages)] \
        == shared_pages
    eng.run_until_idle()

    # oracle: same prompt, fresh engine with no cache
    out = _run(_engine(cfg, params, "paged", name="pe-oracle"), [pB],
               max_new=5)
    assert rB.output_tokens == out[0]


# ---------------------------------------------------------------------------
# Allocator churn: preempt / evict / reset keep pool + tables consistent
# ---------------------------------------------------------------------------

def _check_invariant(alloc: PageAllocator):
    assert alloc.free_pages + alloc.private_pages + alloc.shared_pages \
        == alloc.num_pages
    assert alloc.free_pages >= 0


def test_allocator_churn_keeps_tables_consistent():
    cfg = BASE
    params = _params(cfg)
    eng = _engine(cfg, params, "paged", num_pages=10, cache=True)
    alloc = eng.scheduler.alloc
    prompts = [np.arange(i, i + 30) % cfg.vocab for i in (2, 5, 9)]
    reqs = [Request(prompt_len=30, max_new_tokens=6,
                    prompt_tokens=np.asarray(p, np.int32)) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.step()                                   # admit + prefill
    _check_invariant(alloc)

    # preempt the youngest running sequence mid-flight
    victim = eng.scheduler.preempt_one()
    assert victim is not None
    _check_invariant(alloc)
    assert alloc.page_table(victim.req_id) == []

    # evict an idle cache block if any, then drain everything
    eng.scheduler.cache.evict_one()
    _check_invariant(alloc)
    eng.run_until_idle()
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert len(r.output_tokens) == 6
    _check_invariant(alloc)

    # preempted victim restarted from scratch: tokens match the oracle
    oracle = _run(_engine(cfg, params, "paged", name="pe-churn-oracle"),
                  [victim.prompt_tokens])
    assert victim.output_tokens == oracle[0]

    eng.scheduler.cache.clear()
    alloc.reset()
    _check_invariant(alloc)
    assert alloc.free_pages == alloc.num_pages


def test_block_tables_fixed_width():
    alloc = PageAllocator(8, page_size=PAGE)
    assert alloc.allocate("s0", 3 * PAGE)
    rows = block_tables(alloc, ["s0"], width=5)
    assert len(rows[0]) == 5 and rows[0][3:] == [-1, -1]
    with pytest.raises(ValueError):
        block_tables(alloc, ["s0"], width=2)


# ---------------------------------------------------------------------------
# KV migration (paged extract -> paged insert)
# ---------------------------------------------------------------------------

def test_paged_migration_preserves_greedy_decode():
    cfg = BASE
    params = _params(cfg)
    engA = _engine(cfg, params, "paged", name="pe-src")
    engB = _engine(cfg, params, "paged", name="pe-dst")
    p = np.arange(1, 28) % cfg.vocab

    ref = _run(_engine(cfg, params, "paged", name="pe-ref"), [p],
               max_new=10)[0]

    r = Request(prompt_len=len(p), max_new_tokens=10,
                prompt_tokens=np.asarray(p, np.int32))
    engA.submit(r)
    while r.generated < 4:
        engA.step()
    state = engA.extract_state(r)
    first4 = list(r.output_tokens)
    engA.scheduler.preempt_one()
    r.generated = 4
    r.prefilled = r.prompt_len
    assert engB.scheduler.admit_direct(r)
    engB.inject_state(r, state)
    engB.run_until_idle()
    assert first4 + r.output_tokens == ref


# ---------------------------------------------------------------------------
# The cache_layout knob
# ---------------------------------------------------------------------------

def test_cache_layout_knob():
    cfg = BASE
    params = _params(cfg)
    eng = _engine(cfg, params, "ring")
    assert eng.get_param("cache_layout") == "ring"
    eng.set_param("cache_layout", "paged")
    assert eng.cache_layout == "paged"
    out = _run(eng, [np.arange(6, 30) % cfg.vocab])
    assert len(out[0]) == 6

    # flipping under live sequences must refuse and leave state intact
    r = Request(prompt_len=20, max_new_tokens=8,
                prompt_tokens=np.arange(20).astype(np.int32))
    eng.submit(r)
    eng.step()
    with pytest.raises(RuntimeError):
        eng.set_param("cache_layout", "ring")
    assert eng.cache_layout == "paged"
    eng.run_until_idle()

    # use_pallas defaults the layout to paged
    eng2 = Engine(cfg.replace(use_pallas=True), params,
                  SchedulerConfig(max_slots=1, num_pages=16,
                                  max_context=128, page_size=PAGE),
                  name="pe-default")
    assert eng2.cache_layout == "paged"


# ---------------------------------------------------------------------------
# mean_step_time rides the MetricBus (the hardware-honesty feedback loop)
# ---------------------------------------------------------------------------

def test_mean_step_time_published_on_bus():
    spec = BUILTIN_SPECS["mean_step_time"]
    assert spec.direction == "lower_better"

    bus = MetricBus()
    col = Collector("node0", bus=bus)
    fired = []
    bus.subscribe("pe-bus.mean_step_time",
                  lambda n, v, t: fired.append((n, v)),
                  above=0.0, edge=False)
    cfg = BASE
    eng = Engine(cfg, _params(cfg),
                 SchedulerConfig(max_slots=1, num_pages=16, max_context=128,
                                 page_size=PAGE),
                 name="pe-bus", collector=col, cache_layout="paged")
    _run(eng, [np.arange(4, 24) % cfg.vocab], max_new=3)
    assert fired and fired[-1][0] == "pe-bus.mean_step_time"
    assert fired[-1][1] == pytest.approx(eng.mean_step_time)
