"""jit'd public wrappers around the Pallas kernels.

Handles model-layout <-> kernel-layout transposes, pads head dims to the
TPU lane width (128) and sublane minimum (8), and picks how a kernel
runs from the default backend: compiled through Mosaic on TPU,
interpreted (``interpret=True``) on the CPU backend the tests use, and
refused on any other backend.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_matmul as _gm
from repro.kernels import paged_decode_attention as _pdec
from repro.kernels import ssm_scan as _ssm

LANE = 128
SUBLANE = 8


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a wrapper's ``interpret`` argument: an explicit value wins;
    otherwise TPU compiles the kernel and CPU interprets it.  Any other
    backend raises, so a kernel never silently runs interpreted on an
    accelerator it was not written for."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend "
        "(compiled on tpu, interpreted on cpu)")


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q, k, v, *, causal=True, window=-1,
                    blk_q=128, blk_k=128, interpret=None):
    """Model layout: q (B,S,H,dh); k,v (B,T,Hkv,dh) -> (B,S,H,dh)."""
    interpret = interpret_mode(interpret)
    b, s, h, dh = q.shape
    t = k.shape[1]
    qt = _pad_to(jnp.moveaxis(q, 2, 1), 3, LANE)       # (B,H,S,dh')
    kt = _pad_to(jnp.moveaxis(k, 2, 1), 3, LANE)
    vt = _pad_to(jnp.moveaxis(v, 2, 1), 3, LANE)
    blk_q = min(blk_q, max(s, SUBLANE))
    blk_k = min(blk_k, t)
    qt = _pad_to(qt, 2, blk_q)
    kt = _pad_to(kt, 2, blk_k)
    vt = _pad_to(vt, 2, blk_k)
    # scale uses the padded dh; rescale q to compensate
    qt = qt * (jnp.sqrt(qt.shape[-1] / dh).astype(qt.dtype))
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    return jnp.moveaxis(out[:, :, :s, :dh], 1, 2)


def decode_attention(q, cache_k, cache_v, kpos, q_pos, *, window=-1,
                     blk_k=128, interpret=None):
    """Model layout: q (B,1,H,dh); cache k/v (B,T,Hkv,dh); kpos (B,T);
    q_pos (B,) -> (B,1,H,dh)."""
    interpret = interpret_mode(interpret)
    b, _, h, dh = q.shape
    t = cache_k.shape[1]
    hkv = cache_k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    qg = _pad_to(_pad_to(qg, 2, SUBLANE), 3, LANE)
    kt = _pad_to(jnp.moveaxis(cache_k, 2, 1), 3, LANE)  # (B,Hkv,T,dh')
    vt = _pad_to(jnp.moveaxis(cache_v, 2, 1), 3, LANE)
    blk_k = min(blk_k, t)
    kt = _pad_to(kt, 2, blk_k)
    vt = _pad_to(vt, 2, blk_k)
    kp = _pad_to(kpos, 1, blk_k) if t % blk_k else kpos
    if kp.shape[1] > t:   # padded slots must be invalid
        kp = kp.at[:, t:].set(-1)
    qg = qg * (jnp.sqrt(qg.shape[-1] / dh).astype(qg.dtype))
    out = _dec.decode_attention(qg, kt, vt, kp, q_pos[:, None],
                                window=window, blk_k=blk_k,
                                interpret=interpret)
    return out[:, :, :g, :dh].reshape(b, 1, h, dh)


def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           window=-1, interpret=None):
    """Paged decode attention over a shared KV page pool.

    Model layout: q (B, 1, H, dh); k_pages/v_pages (N_pages, Hkv, dh,
    page) — the pool as stored, tokens along the minor axis, which the
    kernel reads in place (only live pages, never the whole pool);
    block_tables (B, P) int32 physical page ids in logical order, -1 =
    unmapped tail; ctx_lens (B,) int32 valid cached tokens.  Returns
    (B, 1, H, dh).
    """
    interpret = interpret_mode(interpret)
    b, _, h, dh = q.shape
    hkv = k_pages.shape[1]
    out = _pdec.paged_decode_attention(q.reshape(b, hkv, h // hkv, dh),
                                       k_pages, v_pages, block_tables,
                                       ctx_lens, window=window,
                                       interpret=interpret)
    return out.reshape(b, 1, h, dh)


def grouped_matmul(x, w, counts, *, interpret=None):
    """x (E,C,d) @ w (E,d,f) with per-expert row counts."""
    interpret = interpret_mode(interpret)
    e, c, d = x.shape
    f = w.shape[2]
    xp = _pad_to(_pad_to(x, 1, SUBLANE), 2, LANE)
    wp = _pad_to(_pad_to(w, 1, LANE), 2, LANE)
    out = _gm.grouped_matmul(xp, wp, counts, interpret=interpret)
    return out[:, :c, :f]


def ssm_scan(q, k, v, log_a, h0, *, chunk=128, interpret=None):
    """Model layout: q,k (B,T,H,dk); v (B,T,H,dv); log_a (B,T,H);
    h0 (B,H,dk,dv) -> (y (B,T,H,dv), hT)."""
    interpret = interpret_mode(interpret)
    b, t, h, dk = q.shape
    dv = v.shape[3]
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    la = jnp.moveaxis(log_a, 2, 1)[..., None]          # (B,H,T,1)
    chunk = min(chunk, t)
    pad_t = (-t) % chunk
    if pad_t:
        qt = _pad_to(qt, 2, chunk)
        kt = _pad_to(kt, 2, chunk)
        vt = _pad_to(vt, 2, chunk)
        la = _pad_to(la, 2, chunk)   # zeros: a=1, k=0 -> state unchanged
    y, hT = _ssm.ssm_scan(qt, kt, vt, la, h0, chunk=chunk,
                          interpret=interpret)
    return jnp.moveaxis(y[:, :, :t], 1, 2), hT
