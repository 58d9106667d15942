"""Operations and bytes that the served work needs, counted from shapes.

These count what the algorithm requires, not what the program happens to
execute: valid prompt tokens and live decode rows only (never the mixed
step's padding or the inactive slots), attention over each row's own
context (causal, within the window), and the head dimension as the
model states it (a kernel that pads 120 to 128 does the padding on its
own time).  ``m`` is a configuration's ``model`` block.

Peaks per device kind are in ``peaks.json``, with their source.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"flops": bf16 FLOP/s, "hbm_bytes": bytes/s, "source": ...}``;
    a device missing from the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; add a row with its source")
    return table[device_kind]


def _itemsize(m: dict) -> int:
    return jnp.dtype(m.get("dtype", "bfloat16")).itemsize


def matmul_params_per_layer(m: dict) -> int:
    """Weights one token meets in one layer's projections and MLP."""
    d, h, hkv, dh, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["d_head"], m["d_ff"])
    return d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * ff


def _keys(m: dict, pos: int) -> int:
    """Keys the query at position ``pos`` attends to."""
    w = m.get("window", -1)
    return min(pos + 1, w) if w and w > 0 else pos + 1


def attention_flops(m: dict, keys: int) -> int:
    """q.k and p.v for one query over ``keys`` keys, all heads, one layer."""
    return 4 * keys * m["n_heads"] * m["d_head"]


def step_flops(m: dict, decode_ctx: list[int], prefill: tuple | None = None
               ) -> float:
    """Model FLOPs of one engine step.

    ``decode_ctx``: for each live decode row, the context it attends to
    (its token included).  ``prefill``: ``(start, n)`` for a chunk of
    ``n`` valid prompt tokens at positions ``start .. start + n - 1``,
    whose last token alone is unembedded."""
    L = m["n_layers"]
    tokens = len(decode_ctx)
    attn = sum(attention_flops(m, c) for c in decode_ctx)
    heads = len(decode_ctx)
    if prefill is not None:
        start, n = prefill
        tokens += n
        # sum over positions p of keys(p), in closed form when no
        # window binds
        w = m.get("window", -1)
        if not w or w <= 0 or start + n <= w:
            keys = n * start + n * (n + 1) // 2
        else:
            keys = sum(_keys(m, p) for p in range(start, start + n))
        attn += 4 * keys * m["n_heads"] * m["d_head"]
        heads += 1
    dense = 2 * tokens * matmul_params_per_layer(m)
    return float(L * (dense + attn) + 2 * heads * m["d_model"] * m["vocab"])


def paged_decode_cost(m: dict, ctx: list[int], page: int) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the paged decode kernel (one layer)
    needs for live rows with contexts ``ctx``: every live K and V page
    read once, q read and the output written once."""
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    item = _itemsize(m)
    live = [c for c in ctx if c > 0]
    flops = sum(4 * c * h * dh for c in live)
    pages = sum(-(-c // page) for c in live)
    kv = 2 * pages * page * hkv * dh * item
    qo = 2 * len(live) * h * dh * item
    return float(flops), float(kv + qo)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes"])
