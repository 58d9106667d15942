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
import math
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.kernels import ops
from repro.serving.engine import Engine
from repro.serving.scheduler import SchedulerConfig

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
        (pages + 1, cfg.n_kv_heads, cfg.d_head, sc.page_size), cfg.dtype,
        sharding=one_chip)
    tables = jax.ShapeDtypeStruct((sc.max_slots, p_max), jnp.int32,
                                  sharding=one_chip)
    ctx = jax.ShapeDtypeStruct((sc.max_slots,), jnp.int32, sharding=one_chip)
    fn = jax.jit(partial(ops.paged_decode_attention, interpret=False))
    compiled = fn.lower(q, pool, pool, tables, ctx).compile()
    print("paged_decode_attention:", compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


# an HLO instruction: name, result type (dims and layout), opcode, operands
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\w+\[[0-9,]*\]"
                     r"(?:\{[^}]*\})?) ([\w-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"\w+\[([0-9,]*)\](?:\{([^:}]*)[^}]*\})?")


def _hlo_ops(text: str):
    """(name, opcode, [(dims, minor-to-major)] of its result, operand
    names) of every instruction of a compiled module's text."""
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            arrays = [(tuple(int(d) for d in dims.split(",") if d),
                       layout) for dims, layout in _HLO_ARRAY.findall(
                           m.group(2))]
            yield (m.group(1), m.group(3), arrays,
                   re.findall(r"%([\w.-]+)", m.group(4)))


def _pool_sized(arrays, size: int) -> bool:
    return any(math.prod(dims) == size for dims, _ in arrays)


# the pools, slots and tables of chipbench's two configurations
BENCH_SHAPES = [
    (1348, 2, 6, 128, 16, 128, -1),     # qwen2-vl-2b
    (225, 8, 4, 120, 11, 32, 4096),     # h2o-danube-3-4b
]


@pytest.mark.parametrize("pool_pages,hkv,g,dh,rows,p_max,window",
                         BENCH_SHAPES)
def test_paged_decode_kernel_compiles_at_benchmark_shapes(
        one_chip, pool_pages, hkv, g, dh, rows, p_max, window):
    """The kernel reads the pool as stored at both head shapes, danube's
    120-wide heads included: in the compiled call nothing but the
    kernel touches a pool-sized array — no copy (to another layout or
    memory), transpose, pad or fusion — and every pool-sized array
    keeps the parameter's layout."""
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape(pool_pages, hkv, dh, 128)
    fn = jax.jit(partial(ops.paged_decode_attention, interpret=False,
                         window=window))
    compiled = fn.lower(shape(rows, 1, hkv * g, dh), pool, pool,
                        shape(rows, p_max, dtype=jnp.int32),
                        shape(rows, dtype=jnp.int32)).compile()
    print("paged_decode_attention:", compiled.memory_analysis())
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    ops_ = list(_hlo_ops(text))
    pooled = {name for name, _, arrays, _ in ops_
              if _pool_sized(arrays, pool.size)}
    layouts = set()
    for name, opcode, arrays, operands in ops_:
        if name in pooled:
            layouts |= {lay for dims, lay in arrays
                        if math.prod(dims) == pool.size}
        if name in pooled or pooled.intersection(operands):
            assert opcode in ("parameter", "bitcast") or \
                name.startswith("paged_decode_attention"), (name, opcode)
    assert layouts == {"3,2,1,0"}, layouts


@pytest.mark.parametrize("pool_pages,hkv,g,dh,rows,p_max,window",
                         BENCH_SHAPES)
def test_engine_decode_step_keeps_the_pool_as_stored(
        smoke, one_chip, monkeypatch, pool_pages, hkv, g, dh, rows, p_max,
        window):
    """The Engine's decode step at chipbench's pool and head shapes (two
    layers): no copy (to another layout or memory), transpose or pad
    instruction makes a layer's pool, and every array of a layer's pool
    size is in the pool's stored layout.  Before the pool was stored
    tokens-minor, the compiler relaid danube's 120-wide pool for the
    kernel and back in every layer of every step.  The layer scan's own
    slice and write-back of each layer (fusions) are not this test's."""
    cfg = smoke.smoke_model().replace(n_layers=2, n_heads=hkv * g,
                                      n_kv_heads=hkv, d_head=dh,
                                      window=window)
    sc = SchedulerConfig(max_slots=rows, max_context=p_max * 128,
                         page_size=128, num_pages=pool_pages - 1)
    params = _on(one_chip, jax.eval_shape(
        lambda: models.init(cfg, jax.random.key(0))))
    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    monkeypatch.setattr(Engine, "_build_cache", lambda self: None)
    eng = Engine(cfg, params, sc, name="aot")
    cache = _on(one_chip, jax.eval_shape(lambda: models.init_cache(
        cfg, sc.max_slots, sc.max_context, layout="paged",
        num_pages=sc.num_pages, page_size=sc.page_size)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    key = _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = eng._decode_paged_fn.lower(
        params, i32(rows, 1), cache, i32(rows, eng._p_max), key,
        temp).compile().as_text()
    assert "tpu_custom_call" in text
    layer = pool_pages * hkv * dh * 128
    for name, opcode, arrays, _ in _hlo_ops(text):
        for dims, layout in arrays:
            if math.prod(dims) == layer:
                assert opcode not in ("copy", "copy-start", "copy-done",
                                      "transpose", "pad"), name
                assert layout == ",".join(
                    str(i) for i in reversed(range(len(dims)))), name


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
