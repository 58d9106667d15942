"""Pure-jnp oracles for every Pallas kernel (the ground truth the sweep
tests in tests/test_kernels.py assert against)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=-1):
    """q: (B,H,S,dh); k,v: (B,Hkv,T,dh) -> (B,H,S,dh)."""
    b, h, s, dh = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, s, dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bhgsd,bhtd->bhgst", qg, kf) / math.sqrt(dh)
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(t)[None, :]
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(jnp.float32))
    return out.reshape(b, h, s, dh).astype(q.dtype)


def decode_attention_ref(q, k, v, kpos, q_pos, *, window=-1):
    """q: (B,Hkv,G,dh); k,v: (B,Hkv,T,dh); kpos: (B,T); q_pos: (B,1)."""
    b, hkv, g, dh = q.shape
    scores = jnp.einsum("bhgd,bhtd->bhgt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    valid = (kpos >= 0) & (kpos <= q_pos)            # (B, T)
    if window > 0:
        valid &= kpos > q_pos - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                               *, window=-1):
    """Gather-then-attend oracle for the paged kernel.  q: (B,Hkv,G,dh);
    k_pages/v_pages: (N,Hkv,dh,page); block_tables: (B,P) int32 (-1 =
    unmapped); ctx_lens: (B,)."""
    b = q.shape[0]
    page = k_pages.shape[-1]
    t = block_tables.shape[1] * page
    ids = jnp.maximum(block_tables, 0)                    # (B, P)
    # (B, P, Hkv, dh, page) -> (B, T, Hkv, dh)
    k = jnp.moveaxis(k_pages[ids], 4, 2).reshape(b, t, *k_pages.shape[1:3])
    v = jnp.moveaxis(v_pages[ids], 4, 2).reshape(b, t, *v_pages.shape[1:3])
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    kpos = jnp.where(kpos < ctx_lens[:, None], kpos, -1)
    qpos = (ctx_lens - 1)[:, None]
    return decode_attention_ref(q, jnp.moveaxis(k, 2, 1),
                                jnp.moveaxis(v, 2, 1), kpos, qpos,
                                window=window)


def grouped_matmul_ref(x, w, counts):
    """x: (E,C,d); w: (E,d,f); counts: (E,) -> (E,C,f) with rows past
    counts zeroed (they are padding)."""
    out = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                     w.astype(jnp.float32))
    c = x.shape[1]
    row = jnp.arange(c)[None, :, None]
    return jnp.where(row < counts[:, None, None], out, 0.0).astype(x.dtype)


def ssm_scan_ref(q, k, v, log_a, h0):
    """Sequential recurrence.  q,k: (B,H,T,dk); v: (B,H,T,dv);
    log_a: (B,H,T,1); h0: (B,H,dk,dv) -> (y (B,H,T,dv), hT)."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    a = jnp.exp(log_a.astype(jnp.float32))[..., 0]   # (B,H,T)

    def step(h, xs):
        qt, kt, vt, at = xs                          # (B,H,dk) ... (B,H)
        h = at[..., None, None] * h + kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhd,bhde->bhe", qt, h)
        return h, y

    xs = (jnp.moveaxis(qf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(a, 2, 0))
    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 2).astype(v.dtype), hT
