"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import contextlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _assert_close(a, b, dtype, tol_f32=2e-5, tol_bf16=2e-2):
    tol = tol_bf16 if dtype == jnp.bfloat16 else tol_f32
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None)])
def test_interpret_mode_follows_the_backend(monkeypatch, backend, want):
    """Compiled on TPU, interpreted on the CPU the tests use, refused
    elsewhere; an explicit argument always wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_mode(True) is True
    assert ops.interpret_mode(False) is False
    if want is None:
        with pytest.raises(RuntimeError, match=backend):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is want


# ---------------------------------------------------------------------------
# flash attention (prefill/training)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hkv,dh", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 8, 2, 64),       # GQA 4:1
    (1, 192, 4, 1, 32),       # MQA, ragged seq vs 128 blocks
    (2, 64, 2, 2, 128),       # short seq, wide head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, hkv, dh, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(jnp.moveaxis(q, 2, 1),
                                   jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1), causal=True)
    _assert_close(out, jnp.moveaxis(want, 1, 2), dtype)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_window(window):
    b, s, h, dh = 1, 256, 4, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
    want = ref.flash_attention_ref(jnp.moveaxis(q, 2, 1),
                                   jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1),
                                   causal=True, window=window)
    _assert_close(out, jnp.moveaxis(want, 1, 2), jnp.float32)


# ---------------------------------------------------------------------------
# decode attention (flash-decoding, split-KV)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,dh,t,qpos", [
    (2, 8, 2, 64, 256, 200),
    (1, 4, 4, 64, 128, 5),      # near-empty cache
    (3, 4, 1, 128, 384, 380),   # MQA, nearly full
])
@pytest.mark.parametrize("window", [-1, 64])
def test_decode_attention_sweep(b, h, hkv, dh, t, qpos, window):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh))
    ck = jax.random.normal(ks[1], (b, t, hkv, dh))
    cv = jax.random.normal(ks[2], (b, t, hkv, dh))
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    # slots past qpos are "unwritten" — mark invalid
    kpos = jnp.where(kpos <= qpos, kpos, -1)
    qp = jnp.full((b,), qpos)
    out = ops.decode_attention(q, ck, cv, kpos, qp, window=window,
                               interpret=True)
    qg = q.reshape(b, hkv, h // hkv, dh)
    want = ref.decode_attention_ref(qg, jnp.moveaxis(ck, 2, 1),
                                    jnp.moveaxis(cv, 2, 1), kpos,
                                    qp[:, None], window=window)
    _assert_close(out.reshape(b, hkv, h // hkv, dh), want, jnp.float32)


def test_decode_attention_tail_not_truncated():
    """Regression: the low-level kernel used nk = t // blk_k, silently
    dropping the last t % blk_k keys from the softmax whenever the cache
    length was not block-divisible."""
    da = importlib.import_module("repro.kernels.decode_attention")
    b, hkv, g, dh, t, blk = 2, 1, 8, 128, 200, 128
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, dh))
    k = jax.random.normal(ks[1], (b, hkv, t, dh))
    v = jax.random.normal(ks[2], (b, hkv, t, dh))
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    qp = jnp.full((b, 1), t - 1)
    out = da.decode_attention(q, k, v, kpos, qp, blk_k=blk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, kpos, qp)
    _assert_close(out, want, jnp.float32)
    # the truncated-softmax bug reproduced by masking the tail away:
    # results must actually depend on those last t % blk_k keys
    trunc = ref.decode_attention_ref(q, k, v,
                                     jnp.where(kpos < blk, kpos, -1), qp)
    assert float(jnp.abs(want - trunc).max()) > 1e-2


# ---------------------------------------------------------------------------
# paged decode attention (block-table indirection over a KV page pool)
# ---------------------------------------------------------------------------

def _paged_case(b, hkv, g, dh, page, per_seq, shared=0, seed=9,
                shuffled=False):
    """Pool + block tables: ``shared`` leading physical pages appear in
    every row (a cached prefix), the rest are per-sequence private;
    ``shuffled`` hands the physical pages out in a random order."""
    n = shared + b * (per_seq - shared)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, hkv * g, dh))
    k_pages = jax.random.normal(ks[1], (n, hkv, dh, page))
    v_pages = jax.random.normal(ks[2], (n, hkv, dh, page))
    rows, nxt = [], shared
    for _ in range(b):
        rows.append(list(range(shared))
                    + list(range(nxt, nxt + per_seq - shared)))
        nxt += per_seq - shared
    bt = np.asarray(rows, np.int32)
    if shuffled:
        bt = np.random.default_rng(seed).permutation(n)[bt]
    return q, k_pages, v_pages, jnp.asarray(bt, jnp.int32)


@contextlib.contextmanager
def _pages_per_block(n, k_pages):
    """Make the kernel take ``n`` pages a grid step at these shapes (its
    budget otherwise fits every page of a test's small tables in one)."""
    pdec = importlib.import_module("repro.kernels.paged_decode_attention")
    was = pdec.BLOCK_BYTES
    pdec.BLOCK_BYTES = n * pdec.slab_bytes(*k_pages.shape[1:],
                                           k_pages.dtype.itemsize)
    pdec.paged_decode_attention.clear_cache()
    try:
        yield
    finally:
        pdec.BLOCK_BYTES = was
        pdec.paged_decode_attention.clear_cache()


def _check_paged(q, k_pages, v_pages, bt, ctx, window=-1):
    """Kernel against the gather-then-attend oracle (zeros for an empty
    row), with the pages a grid step takes at these shapes and with two
    pages a step, so rows span several blocks, most with a partial last
    one."""
    b, _, h, dh = q.shape
    hkv = k_pages.shape[1]
    want = ref.paged_decode_attention_ref(q.reshape(b, hkv, h // hkv, dh),
                                          k_pages, v_pages, bt, ctx,
                                          window=window)
    want = jnp.where((ctx > 0)[:, None, None, None], want, 0)
    out = ops.paged_decode_attention(q, k_pages, v_pages, bt, ctx,
                                     window=window, interpret=True)
    _assert_close(out.reshape(b, hkv, h // hkv, dh), want, q.dtype)
    with _pages_per_block(2, k_pages):
        out = ops.paged_decode_attention(q, k_pages, v_pages, bt, ctx,
                                         window=window, interpret=True)
    _assert_close(out.reshape(b, hkv, h // hkv, dh), want, q.dtype)


@pytest.mark.parametrize("b,hkv,g,dh,page,per_seq", [
    (2, 2, 4, 64, 16, 4),       # GQA
    (1, 1, 1, 128, 32, 3),      # MQA, single row, wide head
    (3, 4, 2, 32, 16, 5),
    (2, 2, 6, 128, 16, 7),      # several blocks, a partial last one
    (2, 8, 4, 120, 16, 3),      # h2o-danube-3-4b's heads: Hkv 8, G 4, dh 120
])
@pytest.mark.parametrize("aligned", [True, False])
def test_paged_decode_attention_sweep(b, hkv, g, dh, page, per_seq,
                                      aligned):
    q, kp, vp, bt = _paged_case(b, hkv, g, dh, page, per_seq)
    full = per_seq * page
    ctx = jnp.full((b,), full, jnp.int32) if aligned else \
        jnp.asarray([full - 1 - 7 * i for i in range(b)], jnp.int32)
    _check_paged(q, kp, vp, bt, ctx)


@pytest.mark.parametrize("lens,shuffled", [
    ([1, 1, 1], False),                 # one cached token
    ([5 * 16, 5 * 16 - 1, 1], False),   # the last position of P pages
    ([40, 0, 77], False),               # an empty row between live rows
    ([80, 33, 61], True),               # physical pages out of order
])
def test_paged_decode_attention_contexts(lens, shuffled):
    q, kp, vp, bt = _paged_case(3, 2, 2, 64, 16, per_seq=5,
                                shuffled=shuffled)
    ctx = jnp.asarray(lens, jnp.int32)
    _check_paged(q, kp, vp, bt, ctx)


def test_paged_decode_attention_shared_prefix_rows():
    b, hkv, g, dh, page, per_seq = 3, 2, 2, 64, 16, 6
    q, kp, vp, bt = _paged_case(b, hkv, g, dh, page, per_seq, shared=2)
    ctx = jnp.asarray([per_seq * page, per_seq * page - 5, 2 * page + 3],
                      jnp.int32)
    _check_paged(q, kp, vp, bt, ctx)
    # two rows given identical tables, lengths AND query must agree
    # exactly — the prefix really is one physical copy
    bt2 = bt.at[1].set(bt[0])
    q2 = q.at[1].set(q[0])
    ctx2 = ctx.at[1].set(ctx[0])
    out = ops.paged_decode_attention(q2, kp, vp, bt2, ctx2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


def test_paged_decode_attention_unmapped_tail():
    # rows of different logical length: short rows carry -1 page ids,
    # which must contribute nothing to the softmax
    b, hkv, g, dh, page = 2, 2, 2, 64, 16
    q, kp, vp, bt = _paged_case(b, hkv, g, dh, page, per_seq=4)
    bt = bt.at[1, 2:].set(-1)                  # row 1 maps only 2 pages
    ctx = jnp.asarray([4 * page - 2, page + 5], jnp.int32)
    _check_paged(q, kp, vp, bt, ctx)


# 40: the window's first token lies inside a page, and with two pages a
# step its first page starts a block the row's first page does not
@pytest.mark.parametrize("window", [24, 64, 40])
def test_paged_decode_attention_window(window):
    b, hkv, g, dh, page = 2, 2, 4, 64, 16
    q, kp, vp, bt = _paged_case(b, hkv, g, dh, page, per_seq=5)
    ctx = jnp.asarray([5 * page - 3, 3 * page + 9], jnp.int32)
    _check_paged(q, kp, vp, bt, ctx, window=window)


def test_paged_decode_attention_bf16():
    b, hkv, g, dh, page = 2, 2, 4, 64, 16
    q, kp, vp, bt = _paged_case(b, hkv, g, dh, page, per_seq=4)
    ctx = jnp.asarray([4 * page, 3 * page - 6], jnp.int32)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    _check_paged(q, kp, vp, bt, ctx)


def test_paged_decode_attention_reads_the_pool_as_stored():
    """The wrapper hands the pool to the kernel as stored: no transpose
    or pad of a pool-sized operand, whose cost would follow the pool and
    not the live context, at either head shape."""
    for hkv, g, dh in ((2, 6, 128), (8, 4, 120)):
        n, page, b, p = 300, 16, 4, 8
        pool = jax.ShapeDtypeStruct((n, hkv, dh, page), jnp.bfloat16)
        text = jax.jit(ops.paged_decode_attention, static_argnames=(
            "window", "interpret")).lower(
            jax.ShapeDtypeStruct((b, 1, hkv * g, dh), jnp.bfloat16),
            pool, pool, jax.ShapeDtypeStruct((b, p), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            interpret=True).as_text()
        moved = [line for line in text.splitlines()
                 if re.search(r"stablehlo\.(transpose|pad)\b", line)
                 and any(np.prod([int(d) for d in t.split("x")[:-1]])
                         >= n * page * hkv * dh
                         for t in re.findall(r"tensor<([0-9x]+x\w+)>", line))]
        assert not moved, moved


# ---------------------------------------------------------------------------
# grouped matmul (MoE experts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,c,d,f", [
    (4, 64, 128, 256),
    (8, 32, 64, 64),
    (2, 128, 256, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(e, c, d, f, dtype):
    ks = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    counts = jnp.array([c, c // 2, 0, 1][:e].ljust if False else
                       [min(c, max(0, c - i * (c // max(e - 1, 1))))
                        for i in range(e)])
    out = ops.grouped_matmul(x, w, counts, interpret=True)
    want = ref.grouped_matmul_ref(x, w, counts)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * d, rtol=tol)


def test_grouped_matmul_empty_experts_are_zero():
    x = jax.random.normal(jax.random.key(4), (4, 16, 32))
    w = jax.random.normal(jax.random.key(5), (4, 32, 64))
    counts = jnp.array([16, 0, 3, 0])
    out = np.asarray(ops.grouped_matmul(x, w, counts, interpret=True))
    assert np.all(out[1] == 0) and np.all(out[3] == 0)
    assert np.all(out[2, 3:] == 0)          # rows past count zeroed


# ---------------------------------------------------------------------------
# chunked SSM scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,t,dk,dv,chunk", [
    (1, 2, 128, 16, 16, 32),
    (2, 4, 96, 32, 16, 32),     # ragged tail chunk
    (1, 1, 64, 64, 64, 64),     # single chunk
])
def test_ssm_scan_sweep(b, h, t, dk, dv, chunk):
    ks = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(ks[0], (b, t, h, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, dv)) * 0.3
    log_a = -jax.random.uniform(ks[3], (b, t, h)) * 0.1
    h0 = jnp.zeros((b, h, dk, dv))
    y, hT = ops.ssm_scan(q, k, v, log_a, h0, chunk=chunk, interpret=True)
    y_ref, hT_ref = ref.ssm_scan_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        jnp.moveaxis(log_a, 2, 1)[..., None], h0)
    _assert_close(y, jnp.moveaxis(y_ref, 1, 2), jnp.float32, tol_f32=1e-4)
    _assert_close(hT, hT_ref, jnp.float32, tol_f32=1e-4)


def test_ssm_scan_nonzero_initial_state():
    b, h, t, dk, dv = 1, 2, 64, 16, 16
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, dv)) * 0.3
    log_a = -jax.random.uniform(ks[3], (b, t, h)) * 0.05
    h0 = jax.random.normal(ks[4], (b, h, dk, dv)) * 0.5
    y, hT = ops.ssm_scan(q, k, v, log_a, h0, chunk=16, interpret=True)
    y_ref, hT_ref = ref.ssm_scan_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        jnp.moveaxis(log_a, 2, 1)[..., None], h0)
    _assert_close(y, jnp.moveaxis(y_ref, 1, 2), jnp.float32, tol_f32=1e-4)
    _assert_close(hT, hT_ref, jnp.float32, tol_f32=1e-4)
