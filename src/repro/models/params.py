"""Parameter-definition trees.

Modules describe parameters as trees of ``P`` (shape + logical axes +
initializer).  Generic walkers produce:
  * initialized pytrees (``init_params``),
  * ``PartitionSpec`` pytrees for pjit (``param_pspecs``),
  * ``ShapeDtypeStruct`` pytrees for AOT lowering (``param_shapes``) —
    the dry-run never allocates real weights.

Logical-axis → mesh-axis mapping lives in ``repro.distributed.sharding``;
this module is mesh-agnostic.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class P:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones
    scale: float = 1.0              # stddev multiplier for 'normal'
    dtype: Optional[str] = None     # override model dtype (e.g. f32 norms)
    fan_in: Optional[int] = None    # inputs per output where the
    #                                 last-but-one dim is not it

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Tree = Any  # nested dict of P / arrays / specs


def stack(defs: Tree, *dims: int) -> Tree:
    """Prepend layer-stack dims (replicated axes) to every P in the tree."""
    def go(p: P) -> P:
        return replace(p, shape=tuple(dims) + p.shape,
                       axes=(None,) * len(dims) + p.axes)
    return jax.tree.map(go, defs, is_leaf=lambda x: isinstance(x, P))


def _init_one(p: P, key: jax.Array, dtype: jnp.dtype) -> jax.Array:
    dt = jnp.dtype(p.dtype) if p.dtype else dtype
    if p.init == "zeros":
        return jnp.zeros(p.shape, dt)
    if p.init == "ones":
        return jnp.ones(p.shape, dt)
    # fan-in scaled normal on the last-but-one "input" dim heuristic:
    fan_in = p.fan_in or (p.shape[-2] if len(p.shape) >= 2 else p.shape[-1])
    std = p.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(dt)


def init_params(defs: Tree, key: jax.Array, dtype: jnp.dtype) -> Tree:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, P))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_shapes(defs: Tree, dtype: jnp.dtype) -> Tree:
    def go(p: P):
        dt = jnp.dtype(p.dtype) if p.dtype else dtype
        return jax.ShapeDtypeStruct(p.shape, dt)
    return jax.tree.map(go, defs, is_leaf=lambda x: isinstance(x, P))


def param_axes(defs: Tree) -> Tree:
    """Tree of logical-axis tuples (consumed by distributed.sharding)."""
    return jax.tree.map(lambda p: p.axes, defs,
                        is_leaf=lambda x: isinstance(x, P))


def count_params(defs: Tree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, P))
    return int(sum(np.prod(p.shape) for p in leaves))


def tp(w: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Pin a weight's tensor-parallel layout at its USE site.

    Under GSPMD, a contraction between a seq-sharded activation and a
    TP-sharded weight has two legal resolutions: gather the (huge)
    weight or gather the (small) activation slice.  The compiler's cost
    model sometimes picks the weight — for llama3-405b that is a 3.5 GB
    full w_out materialization per layer.  Constraining the weight here
    makes gathering it illegal, so the activation moves instead — the
    Megatron weight-stationary schedule.

    ``axes`` entries are 'model' or None (trailing stack dims are
    handled automatically).  No-op without a mesh, when the dim is not
    divisible, or when sharding is disabled.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or "model" not in mesh.axis_names:
            return w
        m = mesh.shape["model"]
        offset = w.ndim - len(axes)       # leading (scan-stack) dims
        entries: list[Optional[str]] = [None] * w.ndim
        for i, a in enumerate(axes):
            if a == "model" and w.shape[offset + i] % m == 0:
                entries[offset + i] = "model"
        if not any(entries):
            return w
        return jax.lax.with_sharding_constraint(
            w, jax.sharding.PartitionSpec(*entries))
    except Exception:
        return w
