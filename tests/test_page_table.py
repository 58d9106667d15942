"""Physical page ids + kernel block tables: the allocator↔kernel page
contract.  Property: under any allocate/share/acquire/promote/free/drop
interleaving the physical ids stay a disjoint partition of
``range(num_pages)`` (free ∪ private ∪ shared), every count matches its
id list, and ``page_table`` rows are consistent.  Plus the end-to-end
check: KV scattered into pages by the allocator's tables attends
identically (interpret mode) to the same KV laid out contiguously."""
import random

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # pragma: no cover - env dependent
    HAVE_HYPOTHESIS = False

    def settings(**kw):                 # no-op decorators so module-level
        return lambda fn: fn            # @settings/@given still evaluate

    def given(*a, **kw):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def skipped():              # zero-arg: no fixture resolution
                pass
            skipped.__name__ = fn.__name__
            skipped.__doc__ = fn.__doc__
            return skipped
        return deco

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **kw: None

    st = _AnyStrategy()

from repro.serving.kv_cache import PageAllocator, block_tables


# ---------------------------------------------------------------------------
# id-partition invariant
# ---------------------------------------------------------------------------

def _ids_partition(a: PageAllocator):
    """free ∪ private ∪ shared ids must tile [0, num_pages) exactly, and
    every count the scheduling plane reads must agree with the id lists
    the kernel plane gathers through."""
    free = list(a._free_ids)
    priv = [i for ids in a._seq_ids.values() for i in ids]
    shared = [i for ids in a._block_ids.values() for i in ids]
    everything = free + priv + shared
    assert len(everything) == a.num_pages, "ids leaked or duplicated"
    assert set(everything) == set(range(a.num_pages))
    assert len(free) == a.free_pages
    assert len(priv) == a.private_pages
    assert len(shared) == a.shared_pages
    for seq, ids in a._seq_ids.items():
        assert len(ids) == a.holds(seq)
    for bid, ids in a._block_ids.items():
        assert len(ids) == a._blocks[bid].pages
        assert a.block_pages(bid) == ids
    # page_table = acquired blocks (in order) then private pages
    for seq in set(a._used) | set(a._seq_blocks):
        want = [i for b in a._seq_blocks.get(seq, ())
                for i in a._block_ids.get(b, ())]
        want += a._seq_ids.get(seq, [])
        assert a.page_table(seq) == want
    # host tier: used ∪ free host ids are exactly host_capacity_pages
    # distinct ids, all outside the HBM range — the spill plane can
    # never leak into (or out of) the partition above
    h_free = list(a._host_free_ids)
    h_used = [i for ids in a._host_ids.values() for i in ids]
    h_all = h_free + h_used
    assert len(h_all) == len(set(h_all)) == a.host_capacity_pages, \
        "host ids leaked or duplicated"
    assert all(i >= a.num_pages for i in h_all)
    assert len(h_used) == a.host_pages
    assert len(h_free) == a.host_free_pages
    assert set(a._host_blocks) == set(a._host_ids)
    for seq in a._host_ids:             # suspended => zero HBM footprint
        assert seq not in a._seq_ids and seq not in a._used
        assert seq not in a._seq_blocks


def _random_walk(a: PageAllocator, ops):
    seqs = [f"s{i}" for i in range(4)]
    blocks = [f"b{i}" for i in range(4)]
    for op, i, n in ops:
        if op == "alloc":
            a.allocate(seqs[i % 4], n)
        elif op == "share":
            a.share(blocks[i % 4], 1 + n % 3)
        elif op == "acquire":
            a.acquire(seqs[i % 4], blocks[n % 4])
        elif op == "promote":
            a.promote(seqs[i % 4], blocks[n % 4], 1 + n % 2)
        elif op == "free":
            a.free(seqs[i % 4])
        elif op == "drop":
            a.drop_block(blocks[i % 4])
        elif op == "suspend":
            a.suspend(seqs[i % 4])
        elif op == "restore":
            a.restore(seqs[i % 4])
        elif op == "drop_susp":
            a.drop_suspended(seqs[i % 4])
        elif op == "setcap":
            a.set_host_capacity(n % 10)
        elif op == "reset":
            a.reset()
        _ids_partition(a)


_OPS = ["alloc", "share", "acquire", "promote", "free", "drop",
        "suspend", "restore", "drop_susp", "setcap", "reset"]


def test_id_partition_random_walk():
    """Deterministic stand-in for the hypothesis property (runs even
    where hypothesis is not installed)."""
    rng = random.Random(11)
    for trial in range(50):
        a = PageAllocator(num_pages=12, page_size=64,
                          host_capacity_pages=6)
        ops = [(rng.choice(_OPS), rng.randrange(4), rng.randrange(500))
               for _ in range(60)]
        _random_walk(a, ops)


@settings(max_examples=200, deadline=None)
@given(st.lists(st.tuples(
    st.sampled_from(_OPS),
    st.integers(0, 3), st.integers(0, 500)), max_size=60))
def test_id_partition_property(ops):
    _random_walk(PageAllocator(num_pages=12, page_size=64,
                               host_capacity_pages=6), ops)


# ---------------------------------------------------------------------------
# host spill tier (tool-call suspend/resume)
# ---------------------------------------------------------------------------

def test_suspend_restore_partition_roundtrip():
    a = PageAllocator(num_pages=8, page_size=64, host_capacity_pages=2)
    assert a.allocate("s0", 3 * 64)
    assert a.promote("s0", "sys", 1)     # 1 shared + 2 private pages
    assert a.acquire("s1", "sys")
    assert a.suspend("s0") == "host"     # only the private pages spill
    _ids_partition(a)
    assert a.is_suspended("s0") and a.host_pages == 2
    assert a.block_refs("sys") == 1      # the sharer keeps the prefix hot
    assert not a.allocate("s0", 64)      # suspended sequences can't grow
    assert a.restore("s0")
    _ids_partition(a)
    assert not a.is_suspended("s0") and a.host_pages == 0
    # the restored table leads with the re-acquired prefix chain
    assert a.page_table("s0")[0] == a.block_pages("sys")[0]
    assert a.holds("s0") == 2
    # a footprint beyond host capacity falls off the ladder to "drop"
    assert a.allocate("big", 5 * 64)
    assert a.suspend("big") == "drop"
    assert not a.is_suspended("big")
    _ids_partition(a)


def test_reset_clears_host_tier():
    a = PageAllocator(num_pages=8, page_size=64, host_capacity_pages=4)
    assert a.allocate("s0", 3 * 64)
    assert a.suspend("s0") == "host"
    assert a.host_pages == 3
    a.reset()
    assert a.host_pages == 0 and a.host_free_pages == 4
    assert not a.is_suspended("s0")
    _ids_partition(a)


# ---------------------------------------------------------------------------
# shared-prefix round trip into kernel block tables
# ---------------------------------------------------------------------------

def test_promote_moves_front_private_ids():
    a = PageAllocator(num_pages=16, page_size=64)
    assert a.allocate("s0", 4 * 64)
    before = a.page_table("s0")
    assert a.promote("s0", "blk", 2)
    # the *front* ids (prefix tokens) became the shared block; the table
    # seen by the kernel is unchanged — same pages, same order
    assert a.block_pages("blk") == before[:2]
    assert a.page_table("s0") == before
    _ids_partition(a)


def test_shared_prefix_rows_repeat_physical_ids():
    a = PageAllocator(num_pages=32, page_size=64)
    assert a.allocate("s0", 3 * 64)
    assert a.promote("s0", "sys", 2)
    # a second sequence acquires the cached prefix, then grows privately
    assert a.acquire("s1", "sys")
    assert a.allocate("s1", 2 * 64)     # 2 private pages after the prefix
    t0, t1 = a.page_table("s0"), a.page_table("s1")
    assert t0[:2] == t1[:2] == a.block_pages("sys")
    assert set(t0[2:]).isdisjoint(t1[2:])
    rows = block_tables(a, ["s0", "s1"], pad_to=6)
    assert [len(r) for r in rows] == [6, 6]
    assert rows[0][:3] == t0 and rows[0][3:] == [-1] * 3
    assert rows[1][:4] == t1 and rows[1][4:] == [-1] * 2
    # freeing the sharer keeps the block resident (refcounted), and the
    # survivor's table is untouched
    a.free("s1")
    assert a.page_table("s0") == t0
    _ids_partition(a)


def test_block_tables_ragged_rows_pad_with_minus_one():
    a = PageAllocator(num_pages=8, page_size=64)
    a.allocate("long", 3 * 64)
    a.allocate("short", 64)
    rows = block_tables(a, ["long", "short"])
    assert len(rows[0]) == len(rows[1]) == 3
    assert rows[1][1:] == [-1, -1]


# ---------------------------------------------------------------------------
# paged vs contiguous attention through allocator layouts (interpret)
# ---------------------------------------------------------------------------

def test_paged_attention_matches_contiguous_through_allocator():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    page, hkv, h, dh = 16, 2, 4, 64
    a = PageAllocator(num_pages=16, page_size=page)
    # s0 prefills 3 pages and promotes its 2-page prefix; s1 acquires the
    # prefix and adds one private page — classic shared-system-prompt
    assert a.allocate("s0", 3 * page)
    assert a.promote("s0", "sys", 2)
    assert a.acquire("s1", "sys")
    assert a.allocate("s1", page)
    tables = block_tables(a, ["s0", "s1"])
    ctx = [3 * page - 3, 2 * page + 7]      # non-page-aligned lengths
    b, width = len(tables), len(tables[0])

    # contiguous ground-truth KV per sequence (shared prefix identical)
    ks = jax.random.split(jax.random.key(3), 3)
    t_max = width * page
    prefix = jax.random.normal(ks[0], (2 * page, hkv, dh))
    k_seq = jax.random.normal(ks[1], (b, t_max, hkv, dh))
    v_seq = jax.random.normal(ks[2], (b, t_max, hkv, dh))
    k_seq = k_seq.at[:, :2 * page].set(prefix)        # same prefix content
    v_seq = v_seq.at[:, :2 * page].set(prefix[::-1])

    # scatter into the physical pool exactly where the tables point
    n_pool = a.num_pages
    k_pages = jnp.zeros((n_pool, hkv, dh, page))       # tokens minor
    v_pages = jnp.zeros((n_pool, hkv, dh, page))
    for i, row in enumerate(tables):
        for j, pid in enumerate(row):
            if pid < 0:
                continue
            span = slice(j * page, (j + 1) * page)
            k_pages = k_pages.at[pid].set(jnp.moveaxis(k_seq[i, span], 0, -1))
            v_pages = v_pages.at[pid].set(jnp.moveaxis(v_seq[i, span], 0, -1))

    q = jax.random.normal(jax.random.key(4), (b, 1, h, dh))
    out_paged = ops.paged_decode_attention(
        q, k_pages, v_pages, jnp.asarray(tables, jnp.int32),
        jnp.asarray(ctx, jnp.int32), interpret=True)

    # contiguous oracle path: ring-style kpos masks per-sequence length
    kpos = jnp.broadcast_to(jnp.arange(t_max)[None], (b, t_max))
    kpos = jnp.where(kpos < jnp.asarray(ctx)[:, None], kpos, -1)
    qp = jnp.asarray(ctx) - 1
    out_contig = ops.decode_attention(q, k_seq, v_seq, kpos, qp,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(out_paged),
                               np.asarray(out_contig), atol=2e-5, rtol=2e-5)
