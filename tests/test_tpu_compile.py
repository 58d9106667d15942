"""Ahead-of-time compiles of the live serving path for one TPU v5e chip.

jaxlib ships the TPU compiler, and it compiles for a chip that is only
described (``v5e:2x2`` device 0), not attached.  It refuses what the
chip would refuse: a kernel Mosaic cannot lower, or a program whose
buffers do not fit the chip's HBM.  Nothing runs, so these tests say
nothing about results or times; ``chip_smoke.py`` runs the same steps.

The topology is described only inside the module fixture: the TPU
library admits one process at a time, so no import, ``skipif`` or
``parametrize`` argument may touch it.  The persistent compile cache is
off around these compiles (an entry written for a described chip cannot
be read back without one).
"""
from __future__ import annotations

import importlib.util
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.kernels import ops
from repro.serving.engine import Engine

# bytes_limit that a v5e chip's device.memory_stats() reports
V5E_HBM_BYTES = 63 << 28          # 15.75 GiB
SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _live_bytes(mem) -> int:
    """Device bytes a compiled program holds while it runs."""
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def _smoke_shapes(smoke, one_chip):
    cfg = smoke.smoke_model()
    params = _on(one_chip, jax.eval_shape(
        lambda: models.init(cfg, jax.random.key(0))))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    pages = smoke.pool_pages(cfg, weight_bytes, V5E_HBM_BYTES)
    return cfg, params, weight_bytes, pages


def test_paged_decode_kernel_compiles_at_agent_1b_width(smoke, one_chip):
    cfg, _, _, pages = _smoke_shapes(smoke, one_chip)
    sc = smoke.scheduler_config(pages)
    p_max = sc.max_context // sc.page_size
    q = jax.ShapeDtypeStruct((sc.max_slots, 1, cfg.n_heads, cfg.d_head),
                             cfg.dtype, sharding=one_chip)
    pool = jax.ShapeDtypeStruct(
        (pages + 1, sc.page_size, cfg.n_kv_heads, cfg.d_head), cfg.dtype,
        sharding=one_chip)
    tables = jax.ShapeDtypeStruct((sc.max_slots, p_max), jnp.int32,
                                  sharding=one_chip)
    ctx = jax.ShapeDtypeStruct((sc.max_slots,), jnp.int32, sharding=one_chip)
    fn = jax.jit(partial(ops.paged_decode_attention, interpret=False))
    compiled = fn.lower(q, pool, pool, tables, ctx).compile()
    print("paged_decode_attention:", compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_engine_step_fits_one_v5e_at_smoke_pool(smoke, one_chip,
                                                monkeypatch, step):
    """The Engine's own jitted step at agent-1b width and the pool size
    ``chip_smoke.py`` reckons for a v5e; the script's reckoning bounds
    what the compiler says the step holds."""
    cfg, params, weight_bytes, pages = _smoke_shapes(smoke, one_chip)
    sc = smoke.scheduler_config(pages)
    # the default backend here is the CPU, where kernels interpret: the
    # described chip compiles them through Mosaic; and the pool is passed
    # as shapes, never allocated
    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    monkeypatch.setattr(Engine, "_build_cache", lambda self: None)
    eng = Engine(cfg, params, sc, name="aot")
    cache = _on(one_chip, jax.eval_shape(lambda: models.init_cache(
        cfg, sc.max_slots, sc.max_context, layout="paged",
        num_pages=pages, page_size=sc.page_size)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    key = _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    tokens, tables = i32(sc.max_slots, 1), i32(sc.max_slots, eng._p_max)
    if step == "decode":
        lowered = eng._decode_paged_fn.lower(params, tokens, cache, tables,
                                             key, temp)
    else:
        lowered = eng._mixed_fn.lower(
            params, tokens, i32(1, eng._mixed_cap), cache, tables,
            i32(1, eng._p_max), i32(), i32(), i32(), key, temp)
    compiled = lowered.compile()        # raises if it does not fit
    mem = compiled.memory_analysis()
    print(f"{step} step at {pages} pages:", mem)
    assert "tpu_custom_call" in compiled.as_text()
    assert _live_bytes(mem) <= V5E_HBM_BYTES
    assert _live_bytes(mem) <= 1.01 * smoke.pool_footprint(
        cfg, weight_bytes, pages)
