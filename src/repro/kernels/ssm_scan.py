"""Chunked decayed linear-recurrence scan — Pallas TPU kernel.

Evaluates   h_t = a_t · h_{t-1} + k_t ⊗ v_t ;  y_t = q_t · h_t
in chunk-parallel form: the grid walks (B, H, T/chunk) with the running
(dk × dv) state in VMEM f32 scratch; within a chunk everything is MXU
matmuls (intra-chunk masked decay attention + inter-chunk carry), i.e.
the mamba-2/SSD restatement of the selective scan that DESIGN.md §3
adopts as the TPU-native form.  Backs hymba's mamba branch and xLSTM's
mLSTM cell (via models/ssm.chunked_linear_attention's identical math).

Layout (from ops.py): q, k (B, H, T, dk); v (B, H, T, dv);
log_a (B, H, T, 1) (per-token log decay, <= 0); h0 (B, H, dk, dv).
Outputs: y (B, H, T, dv); h_final (B, H, dk, dv).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, la_ref, h0_ref, y_ref, hT_ref, h_ref, *,
            chunk: int):
    jc = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(jc == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)          # (chunk, dk)
    k = k_ref[0, 0].astype(jnp.float32)          # (chunk, dk)
    v = v_ref[0, 0].astype(jnp.float32)          # (chunk, dv)
    la = la_ref[0, 0].astype(jnp.float32)        # (chunk, 1)
    h = h_ref[...]                               # (dk, dv)

    L = jnp.cumsum(la, axis=0)                   # inclusive, (chunk, 1)
    # intra-chunk: S_ij = (q_i · k_j) exp(L_i - L_j), j <= i
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ldiff = L - L[:, 0][None, :]                 # (chunk_i, chunk_j)
    decay = jnp.where(lj <= li, jnp.exp(ldiff), 0.0)
    y = jax.lax.dot_general(s * decay, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: y_i += exp(L_i) q_i · h_prev
    y += jnp.exp(L) * jax.lax.dot_general(
        q, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # carry: h = exp(L_last) h + sum_j exp(L_last - L_j) k_j v_j^T
    l_last = L[chunk - 1, 0]
    rem = jnp.exp(l_last - L)                    # (chunk, 1)
    kv = jax.lax.dot_general(k * rem, v, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    h_ref[...] = jnp.exp(l_last) * h + kv

    @pl.when(jc == nc - 1)
    def _fin():
        hT_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
             h0: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """q,k: (B,H,T,dk); v: (B,H,T,dv); log_a: (B,H,T,1); h0: (B,H,dk,dv).
    T must be a multiple of ``chunk`` (ops.py pads)."""
    b, h, t, dk = q.shape
    dv = v.shape[3]
    chunk = min(chunk, t)
    nc = t // chunk

    kern = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b_, h_, j: (b_, h_, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_a, h0)
