"""Seeded random weights, made by the benchmark and not by the program.

``make`` draws a dense GQA decoder's weights on the device in one jitted
call, in the dtype they are served in (matrices in the configuration's
dtype, norm scales in float32).  The reference calls it again from the
same seed, so it never takes an array the program made or held.
``to_program`` lays the same arrays out as the program's parameter tree,
and checks that tree against ``models.init``'s shapes.

Scales are fan-in: every projection is drawn with standard deviation
1/sqrt(inputs), so q.k scores are of order one and the logits' standard
deviation is about one; norm scales are 1 + 0.1 N(0, 1), so a scale that
went unapplied would show.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def shapes(m: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale)."""
    L, d, h, hkv = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    dh, ff, v = m["d_head"], m["d_ff"], m["vocab"]
    out = {
        "embed": ((v, d), d),
        "final_norm": ((d,), None),
        "norm1": ((L, d), None),
        "norm2": ((L, d), None),
        "wq": ((L, d, h, dh), d),
        "wk": ((L, d, hkv, dh), d),
        "wv": ((L, d, hkv, dh), d),
        "wo": ((L, h, dh, d), h * dh),
        "w_gate": ((L, d, ff), d),
        "w_up": ((L, d, ff), d),
        "w_down": ((L, ff, d), ff),
    }
    if not m.get("tie_embeddings", False):
        out["unembed"] = ((d, v), d)
    return out


@partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, key):
    out = {}
    for i, (name, shape, fan_in, dtype) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, shape, jnp.float32)
        if fan_in is None:
            out[name] = 1.0 + 0.1 * x
        else:
            out[name] = (x / fan_in ** 0.5).astype(dtype)
    return out


def make(m: dict, seed: int) -> dict:
    """Canonical weights of model sizes ``m`` (a configuration's
    ``model`` block) from ``seed``."""
    spec = tuple((name, shape, fan_in, m.get("dtype", "bfloat16"))
                 for name, (shape, fan_in) in sorted(shapes(m).items()))
    return _make(spec, jax.random.key(seed))


def to_program(w: dict, cfg) -> dict:
    """The program's parameter tree over the same arrays (no copies)."""
    from repro import models
    params = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "decoder": [{"e0": {
            "norm1": {"scale": w["norm1"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "norm2": {"scale": w["norm2"]},
            "mlp": {"w_in": w["w_up"], "w_gate": w["w_gate"],
                    "w_out": w["w_down"]},
        }}],
    }
    if "unembed" in w:
        params["unembed"] = {"w": w["unembed"]}
    want = jax.eval_shape(lambda: models.init(cfg, jax.random.key(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(
            "the program's parameter tree no longer matches the benchmark's "
            f"layout:\nprogram {want}\nbenchmark {got}")
    return params
