"""Plain reference of a dense decoder with grouped-query attention.

Pre-norm blocks as Llama, Qwen2 and Mistral publish them: RMSNorm, q/k/v
projections with rotary embeddings (the two halves of each head rotated,
inverse frequencies theta^(-2i/d_head)), causal softmax attention (within
``window`` keys where one is set), an output projection, RMSNorm, and a
SwiGLU MLP, down(silu(gate(x)) * up(x)); a final RMSNorm and the
unembedding (the embedding table's transpose when tied).

It imports nothing of the program.  It runs in float32 with every
matmul at HIGHEST precision, over a whole sequence, one layer at a time
(a scan over the stacked weights, each layer's bf16 weights cast to
float32 as it is used) and with queries in blocks, so that it fits one
chip once the program's state is freed.

``quant="fp8"`` is the control: the same forward with both operands of
every projection, MLP and unembedding matmul rounded to float8_e4m3fn
(per-tensor absmax scaling), the precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_table(n: int, d_head: int, theta: float):
    """cos, sin of shape (n, d_head/2), computed in float64."""
    half = d_head // 2
    inv = float(theta) ** (-np.arange(half, dtype=np.float64) * 2 / d_head)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, window: int, qblock: int):
    """q (T, H, dh), k/v (T, Hkv, dh), causal; queries in blocks."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, h // hkv, dh)
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * qblock, qblock, 0)
        qpos = i * qblock + jnp.arange(qblock)
        s = jnp.einsum("qhgd,thd->hgqt", qb, k, precision=HI,
                       preferred_element_type=jnp.float32) / np.sqrt(dh)
        ok = kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqt,thd->qhgd", p, v, precision=HI,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(block, jnp.arange(t // qblock))
    return out.reshape(t, h, dh)


@partial(jax.jit, static_argnames=("m", "quant"))
def _logits(w, tokens, idx, cos, sin, *, m, quant):
    md = dict(m)
    eps = md["norm_eps"]
    window = md.get("window", -1) or -1
    t = tokens.shape[0]
    qblock = min(512, t)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layers = {k: w[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        h = _rms(x, lw["norm1"], eps)
        q = _rope(_mm("td,dhk->thk", h, lw["wq"], quant), cos, sin)
        k = _rope(_mm("td,dhk->thk", h, lw["wk"], quant), cos, sin)
        v = _mm("td,dhk->thk", h, lw["wv"], quant)
        a = _attention(q, k, v, window, qblock)
        x = x + _mm("thk,hkd->td", a, lw["wo"], quant)
        h = _rms(x, lw["norm2"], eps)
        g = _mm("td,df->tf", h, lw["w_gate"], quant)
        u = _mm("td,df->tf", h, lw["w_up"], quant)
        return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"],
                       quant), None

    x, _ = jax.lax.scan(layer, x, layers)
    hf = _rms(x[idx], w["final_norm"], eps)
    if "unembed" in w:
        return _mm("nd,dv->nv", hf, w["unembed"], quant)
    return _mm("nd,vd->nv", hf, w["embed"], quant)


def _bucket(n: int, least: int) -> int:
    return max(least, 1 << (int(n) - 1).bit_length())


def logits_at(w, m: dict, tokens: np.ndarray, positions: np.ndarray,
              quant: str | None = None):
    """Logits (len(positions), vocab) after ``tokens[:p + 1]`` for each
    position p.  Sequence and position counts are padded to powers of
    two (the padding sits after every real token, so causality keeps it
    out), so a few shapes compile."""
    t = _bucket(len(tokens), 1024)
    n = _bucket(len(positions), 64)
    tok = np.zeros(t, np.int32)
    tok[:len(tokens)] = tokens
    idx = np.zeros(n, np.int32)
    idx[:len(positions)] = positions
    cos, sin = rope_table(t, m["d_head"], m["rope_theta"])
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in m.items()))
    out = _logits(w, jnp.asarray(tok), jnp.asarray(idx), cos, sin, m=key,
                  quant=quant)
    return out[:len(positions)]
