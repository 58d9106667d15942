#!/usr/bin/env python3
"""Compile a configuration's two window executables for a described TPU
v5e chip, with no chip attached, and print their memory analysis.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py <config-name> [--pages N]

The Engine's own ``_decode_paged`` and ``_mixed_step`` are lowered at
the configuration's slots, ``max_context``, chunk capacity and page
count (``--pages`` to try another), for ``v5e:2x2`` device 0.  The
compiler refuses a program that does not fit the chip's HBM; this is how
each configuration's ``num_pages`` was chosen.  Nothing runs, so this
says nothing about results or times.
"""
import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]

V5E_HBM_BYTES = 63 << 28          # bytes_limit a v5e reports: 15.75 GiB


def live_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--pages", type=int, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import spec, weights
    from repro import models
    from repro.kernels import ops
    from repro.serving.engine import Engine

    jax.config.update("jax_enable_compilation_cache", False)
    config = spec.load_json(spec.HERE / "configs" / f"{args.config}.json")
    if args.pages:
        config["engine"]["num_pages"] = args.pages
    cfg = spec.model_config(config)
    sc = spec.scheduler_config(config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on(jax.eval_shape(
        lambda: weights.to_program(weights.make(config["model"], 0), cfg)))
    # kernels compile through Mosaic for the described chip, and the pool
    # is passed as shapes, never allocated
    ops.interpret_mode = lambda interpret=None: False
    Engine._build_cache = lambda self: None
    eng = Engine(cfg, params, sc, name="aot")
    cache = on(jax.eval_shape(lambda: models.init_cache(
        cfg, sc.max_slots, sc.max_context, layout="paged",
        num_pages=sc.num_pages, page_size=sc.page_size)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    tokens, tables = i32(sc.max_slots, 1), i32(sc.max_slots, eng._p_max)
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    print(f"{args.config}: pages {sc.num_pages} slots {sc.max_slots} "
          f"max_context {sc.max_context} weights {weight_bytes}")
    for step, lowered in (
            ("decode", lambda: eng._decode_paged_fn.lower(
                params, tokens, cache, tables, key, temp)),
            ("mixed", lambda: eng._mixed_fn.lower(
                params, tokens, i32(1, eng._mixed_cap), cache, tables,
                i32(1, eng._p_max), i32(), i32(), i32(), key, temp))):
        compiled = lowered().compile()
        mem = compiled.memory_analysis()
        print(f"  {step}: live {live_bytes(mem)} of {V5E_HBM_BYTES} "
              f"(arguments {mem.argument_size_in_bytes}, temp "
              f"{mem.temp_size_in_bytes}, output {mem.output_size_in_bytes}, "
              f"aliased {mem.alias_size_in_bytes}); kernel "
              f"{'tpu_custom_call' in compiled.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
