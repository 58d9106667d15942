"""Kernel microbench: correctness deltas vs oracle, VMEM working set and
arithmetic intensity.

Kernels run in interpret mode here, so wall-clock numbers would measure
the Python interpreter, not the kernel.  What this reports per
kernel/shape is (a) max |err| vs the pure-jnp oracle, (b) the BlockSpec
working set vs the 16 MB/core VMEM budget, and (c) the FLOPs per byte
of HBM traffic.  Kernel time on the chip is the chip benchmark's
(``chipbench/``, e.g. ``paged_decode_attention_roofline``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Report
from repro.kernels import ops, ref

VMEM = 16 * 2**20


def flash_attention_row(rep, b, s, h, hkv, dh, blk=128):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = jnp.moveaxis(ref.flash_attention_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
        jnp.moveaxis(v, 2, 1), causal=True), 1, 2)
    err = float(jnp.abs(out - want).max())
    dhp = max(dh, 128)
    vmem = (blk * dhp * 3 + blk * blk + blk * dhp) * 4
    flops = 4.0 * b * h * s * s * dh / 2            # causal half
    bytes_ = (q.size + k.size + v.size + out.size) * 2   # bf16 on TPU
    rep.add(f"kernels.flash_attention.b{b}s{s}h{h}kv{hkv}d{dh}",
            max_err=f"{err:.2e}",
            vmem_kb=vmem // 1024, vmem_ok=vmem < VMEM,
            intensity=f"{flops/bytes_:.0f}")


def decode_attention_row(rep, b, h, hkv, dh, t, blk=128):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    ck = jax.random.normal(ks[1], (b, t, hkv, dh), jnp.float32)
    cv = jax.random.normal(ks[2], (b, t, hkv, dh), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    qp = jnp.full((b,), t - 1)
    out = ops.decode_attention(q, ck, cv, kpos, qp, interpret=True)
    want = ref.decode_attention_ref(
        q.reshape(b, hkv, h // hkv, dh), jnp.moveaxis(ck, 2, 1),
        jnp.moveaxis(cv, 2, 1), kpos, qp[:, None])
    err = float(jnp.abs(out.reshape(b, hkv, h // hkv, dh) - want).max())
    flops = 4.0 * b * h * t * dh
    bytes_ = (ck.size + cv.size) * 2                 # KV read dominates
    g = h // hkv
    vmem = (max(g, 8) * max(dh, 128) + 2 * blk * max(dh, 128)) * 4
    rep.add(f"kernels.decode_attention.b{b}h{h}kv{hkv}d{dh}t{t}",
            max_err=f"{err:.2e}",
            vmem_kb=vmem // 1024, vmem_ok=vmem < VMEM,
            intensity=f"{flops/bytes_:.1f}")


def paged_decode_attention_row(rep, b, h, hkv, dh, page, per_seq, shared):
    """Block-table-indirected decode over a shared page pool: ``shared``
    prefix pages are the *same* physical ids in every row, so the pool
    holds (and HBM reads) one copy of the prefix per step."""
    n = shared + b * (per_seq - shared)
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n, hkv, dh, page), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n, hkv, dh, page), jnp.float32)
    rows, nxt = [], shared
    for _ in range(b):
        rows.append(list(range(shared))
                    + list(range(nxt, nxt + per_seq - shared)))
        nxt += per_seq - shared
    bt = jnp.asarray(rows, jnp.int32)
    ctx = jnp.asarray([per_seq * page - 1 - 5 * i for i in range(b)],
                      jnp.int32)
    g = h // hkv
    out = ops.paged_decode_attention(q, k_pages, v_pages, bt, ctx,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q.reshape(b, hkv, g, dh),
                                          k_pages, v_pages, bt, ctx)
    err = float(jnp.abs(out.reshape(b, hkv, g, dh) - want).max())
    t = per_seq * page
    # unique pages read once: shared prefix pages are not re-read per row
    uniq_toks = n * page
    dhp = max(dh, 128)
    vmem = (max(g, 8) * dhp + 2 * page * dhp) * 4
    rep.add(f"kernels.paged_decode_attention."
            f"b{b}h{h}kv{hkv}d{dh}p{page}x{per_seq}s{shared}",
            max_err=f"{err:.2e}",
            vmem_kb=vmem // 1024, vmem_ok=vmem < VMEM,
            shared_read_saving=f"{1 - uniq_toks/(b*t):.0%}")


def grouped_matmul_row(rep, e, c, d, f):
    ks = jax.random.split(jax.random.key(2), 2)
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32)
    w = jax.random.normal(ks[1], (e, d, f), jnp.float32)
    counts = jnp.array([c] * (e // 2) + [0] * (e - e // 2))
    out = ops.grouped_matmul(x, w, counts, interpret=True)
    want = ref.grouped_matmul_ref(x, w, counts)
    err = float(jnp.abs(out - want).max())
    vmem = (128 * 128 * 3 + 128 * 128) * 4
    rep.add(f"kernels.grouped_matmul.e{e}c{c}d{d}f{f}",
            max_err=f"{err:.2e}", vmem_kb=vmem // 1024, vmem_ok=True,
            skip_saving=f"{e//2}/{e} experts idle")


def ssm_scan_row(rep, b, h, t, dk, dv, chunk=128):
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (b, t, h, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, dv)) * 0.3
    la = -jax.random.uniform(ks[3], (b, t, h)) * 0.1
    h0 = jnp.zeros((b, h, dk, dv))
    y, hT = ops.ssm_scan(q, k, v, la, h0, chunk=min(chunk, t),
                         interpret=True)
    yr, hr = ref.ssm_scan_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                              jnp.moveaxis(v, 2, 1),
                              jnp.moveaxis(la, 2, 1)[..., None], h0)
    err = float(jnp.abs(y - jnp.moveaxis(yr, 1, 2)).max())
    c = min(chunk, t)
    vmem = (3 * c * max(dk, 128) + c * c + dk * dv) * 4
    rep.add(f"kernels.ssm_scan.b{b}h{h}t{t}dk{dk}dv{dv}",
            max_err=f"{err:.2e}",
            vmem_kb=vmem // 1024, vmem_ok=vmem < VMEM)


def main(report: Report | None = None) -> Report:
    rep = report or Report("kernels: oracle deltas + VMEM working sets")
    flash_attention_row(rep, 1, 512, 8, 2, 128)
    flash_attention_row(rep, 2, 256, 4, 4, 64)
    decode_attention_row(rep, 4, 8, 2, 128, 1024)
    decode_attention_row(rep, 2, 4, 4, 64, 256)
    paged_decode_attention_row(rep, 4, 8, 2, 128, 128, 8, 4)
    paged_decode_attention_row(rep, 2, 4, 4, 64, 16, 4, 0)
    grouped_matmul_row(rep, 8, 128, 256, 512)
    ssm_scan_row(rep, 1, 4, 256, 64, 64)
    rep.note("kernels: interpret-mode correctness vs ref.py oracle; "
             "VMEM working sets within the 16MB/core budget")
    return rep


if __name__ == "__main__":
    print(main().render())
