"""Launch layer on a 1-device mesh: train/serve steps lower, compile AND
run with real numerics; collective parsing; cost extrapolation helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config, get_smoke
from repro.configs.base import ShapeConfig
from repro.launch import specs as specs_mod
from repro.launch.dryrun import (_lin, _period, _scaled_cfg,
                                 collective_bytes, cpu_bf16_inflation,
                                 model_flops)
from repro.launch.mesh import make_mesh
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import (AdamWConfig, TrainPlan, abstract_state,
                                make_train_step, opt_pspecs)
from repro.optim.adamw import adamw_init


def test_train_step_runs_and_learns():
    cfg = get_config("tiny-agent")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", 32, 4, "train")
    acfg = AdamWConfig(lr=5e-3, warmup_steps=0)
    with jax.set_mesh(mesh):
        step, _ = make_train_step(cfg, mesh, TrainPlan(microbatch=2),
                                  acfg, shape=shape)
        params = models.init(cfg, jax.random.key(0))
        opt = adamw_init(params, acfg)
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab, (4, 32)).astype(np.int32)
        batch = {"tokens": toks, "labels": toks}
        losses = []
        for _ in range(12):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3      # memorizes a fixed batch


def test_serve_step_matches_models_decode():
    cfg = get_config("tiny-agent")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("d", 64, 2, "decode")
    with jax.set_mesh(mesh):
        step, _ = make_serve_step(cfg, mesh, shape)
        params = models.init(cfg, jax.random.key(0))
        ctx = specs_mod.decode_context(shape)
        cache = models.init_cache(cfg, 2, ctx)
        toks = jnp.array([[3], [5]], jnp.int32)
        logits, cache2 = step(params, toks, cache)
        ref_cache = models.init_cache(cfg, 2, ctx)
        ref_logits, _ = models.decode_step(params, cfg, toks, ref_cache)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               atol=1e-4)


def test_prefill_step_runs():
    cfg = get_config("tiny-agent")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("p", 32, 2, "prefill")
    with jax.set_mesh(mesh):
        step, _ = make_prefill_step(cfg, mesh, shape)
        params = models.init(cfg, jax.random.key(0))
        cache = models.init_cache(cfg, 2, 32)
        toks = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % cfg.vocab
        logits, cache = step(params, toks, cache)
        assert logits.shape == (2, cfg.vocab)
        assert int(cache["pos"][0]) == 32


def test_opt_pspecs_structure_matches_state():
    cfg = get_smoke("llama3-405b")
    mesh = make_mesh((1, 1), ("data", "model"))
    for int8 in (False, True):
        acfg = AdamWConfig(int8_moments=int8)
        spec = opt_pspecs(cfg, mesh, acfg)
        _, state = abstract_state(cfg, acfg)
        assert (jax.tree.structure(spec) == jax.tree.structure(state))


# ---------------------------------------------------------------------------
# dry-run helpers
# ---------------------------------------------------------------------------

HLO = """
  %ag = bf16[32,1024]{1,0} all-gather(%x), replica_groups=...
  %ar.1 = f32[8,128]{1,0} all-reduce(%y), to_apply=%sum
  %rs = (bf16[16,64]{1,0}, bf16[16,64]{1,0}) reduce-scatter(%a, %b)
  %dot = f32[128,128]{1,0} dot(%p, %q)
  %a2a = s8[4,4]{1,0} all-to-all(%z)
"""


def test_collective_bytes_parsing():
    c = collective_bytes(HLO)
    assert c["all-gather"] == 32 * 1024 * 2
    assert c["all-reduce"] == 8 * 128 * 4
    assert c["reduce-scatter"] == 2 * 16 * 64 * 2
    assert c["all-to-all"] == 16
    assert c["count"] == 4
    assert c["total"] == sum(c[k] for k in
                             ("all-gather", "all-reduce", "reduce-scatter",
                              "all-to-all", "collective-permute"))


def test_cpu_bf16_inflation_detection():
    hlo = """
  %big16 = bf16[4096,16384]{1,0} fusion(%a)
  %big32 = f32[4096,16384]{1,0} convert(%big16)
  %small = f32[16,16]{1,0} convert(%c)
"""
    assert cpu_bf16_inflation(hlo) == 4096 * 16384 * 4


def test_scaled_cfg_periods():
    gem = get_config("gemma3-27b")
    assert _period(gem) == 6
    small = _scaled_cfg(gem, 2)
    assert small.n_layers == 12
    assert not small.scan_layers
    xl = get_config("xlstm-350m")
    assert _period(xl) == 8
    kimi = get_config("kimi-k2-1t-a32b")
    assert _period(kimi) == 1
    assert _scaled_cfg(kimi, 3).n_layers == kimi.first_k_dense + 3


def test_linear_extrapolation_exact_on_linear_data():
    fa = {"flops": 10.0, "bytes_accessed": 6.0,
          "collectives": {"all-gather": 4, "total": 4, "count": 2}}
    fb = {"flops": 16.0, "bytes_accessed": 8.0,
          "collectives": {"all-gather": 6, "total": 6, "count": 3}}
    out = _lin(fa, fb, 2, 4, 10)
    assert out["flops"] == pytest.approx(34.0)       # 4 + 3*u
    assert out["bytes_accessed"] == pytest.approx(14.0)   # 4 + 1*u
    assert out["collectives"]["all-gather"] == pytest.approx(12.0)
    assert out["collectives"]["count"] == 6      # 1 + 0.5*u


def test_model_flops_shapes():
    from repro.configs import SHAPES
    cfg = get_config("llama3-405b")
    n = 405e9
    mf = model_flops(cfg, SHAPES["train_4k"])
    assert 0.7 * 6 * n * 4096 * 256 < mf < 1.5 * 6 * n * 4096 * 256
    # MoE uses active params only
    kimi = get_config("kimi-k2-1t-a32b")
    mf_k = model_flops(kimi, SHAPES["train_4k"])
    assert mf_k < 6 * 500e9 * 4096 * 256      # far below total-param count
