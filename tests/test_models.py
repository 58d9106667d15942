"""Per-architecture smoke tests: reduced configs, one forward/train step
on CPU, output shapes + finiteness, and prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import ARCHS, get_config, get_smoke


def _batch(cfg, b=2, s=32):
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "patch":
        batch["vision_embeds"] = jnp.ones((b, 8, cfg.d_model),
                                          cfg.dtype) * 0.01
        batch["positions"] = models.default_positions(cfg, b, s)
    if cfg.is_encdec:
        batch["frames"] = jnp.ones((b, 16, cfg.d_model), cfg.dtype) * 0.01
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    params = models.init(cfg, jax.random.key(0))
    batch = _batch(cfg)
    loss, metrics = models.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss)), arch
    assert 0.0 < float(loss) < 20.0
    grads = jax.grad(lambda p: models.loss_fn(p, cfg, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke(arch)
    params = models.init(cfg, jax.random.key(0))
    b, s = 2, 32
    batch = _batch(cfg, b, s)
    cache = models.init_cache(cfg, b, 64, 16 if cfg.is_encdec else 0)
    logits_p, cache = models.prefill(
        params, cfg, batch["tokens"], cache,
        vision_embeds=batch.get("vision_embeds"),
        positions=batch.get("positions"),
        frames=batch.get("frames"))
    assert logits_p.shape == (b, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits_p, np.float32)))
    nxt = jnp.argmax(logits_p, -1)[:, None]
    logits_d, cache = models.decode_step(params, cfg, nxt, cache)
    assert logits_d.shape == (b, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits_d, np.float32)))
    assert int(cache["pos"][0]) == s + 1


@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "xlstm-350m",
                                  "kimi-k2-1t-a32b"])
def test_decode_matches_forward(arch):
    """Greedy decode over the cache must agree with teacher-forced
    forward logits (same positions, full attention context).
    capacity_factor is raised so MoE dispatch never drops — prefill
    (t=23) and forward (t=24) otherwise round capacity differently."""
    cfg = get_smoke(arch).replace(dtype="float32", capacity_factor=8.0)
    params = models.init(cfg, jax.random.key(0))
    b, s = 1, 24
    tokens = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab)
    full_logits, _ = models.forward(params, cfg, tokens)

    cache = models.init_cache(cfg, b, 64)
    _, cache = models.prefill(params, cfg, tokens[:, :s - 1], cache)
    step_logits, _ = models.decode_step(params, cfg, tokens[:, s - 1:], cache)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned dimensions."""
    expect = {
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h, arch
        assert cfg.n_kv_heads == kv, arch
        # MoE archs list the per-expert FF width in the assignment
        assert ff in (cfg.d_ff, cfg.d_ff_expert), arch
        assert cfg.vocab == v, arch


def test_moe_configs():
    arctic = get_config("arctic-480b")
    assert arctic.n_experts == 128 and arctic.top_k == 2
    assert arctic.dense_residual
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.n_experts == 384 and kimi.top_k == 8


def test_param_count_scales():
    """Full-config param counts are in the right ballpark."""
    approx = {"llama3-405b": 405e9, "arctic-480b": 480e9,
              "kimi-k2-1t-a32b": 1.0e12, "gemma3-27b": 27e9,
              "h2o-danube-3-4b": 4e9, "qwen2-vl-2b": 2e9,
              "hymba-1.5b": 1.5e9, "xlstm-350m": 350e6}
    for arch, n in approx.items():
        got = models.param_count(get_config(arch))
        assert 0.55 * n < got < 1.6 * n, (arch, got, n)


def test_scan_vs_unroll_equivalence():
    cfg = get_smoke("gemma3-27b").replace(dtype="float32")
    params = models.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    l1, _ = models.loss_fn(params, cfg, batch)
    l2, _ = models.loss_fn(params, cfg.replace(scan_layers=False), batch)
    assert abs(float(l1) - float(l2)) < 1e-5


def test_long_500k_skip_rules():
    """Assignment rule: long_500k runs only on sub-quadratic archs."""
    from repro.configs import SHAPES, shape_applicable
    runs = {a: shape_applicable(get_config(a), SHAPES["long_500k"])[0]
            for a in ARCHS}
    assert runs == {
        "h2o-danube-3-4b": True,          # pure SWA
        "llama3-405b": False,
        "command-r-plus-104b": False,
        "gemma3-27b": True,               # 5:1 local:global
        "arctic-480b": False,
        "kimi-k2-1t-a32b": False,
        "qwen2-vl-2b": False,
        "hymba-1.5b": True,               # hybrid
        "xlstm-350m": True,               # recurrent
        "seamless-m4t-large-v2": False,
    }


def test_moe_groupwise_matches_global_dispatch():
    """The GShard-style per-row dispatch must agree with the global-sort
    path up to capacity-dropping differences (none at low load)."""
    import jax.numpy as jnp
    from repro.models import moe as moe_mod
    cfg = get_smoke("kimi-k2-1t-a32b").replace(dtype="float32",
                                               capacity_factor=4.0)
    spec = cfg.plan()[-1].pattern[0][0]
    params = models.init(cfg, jax.random.key(0))
    # one decoder moe layer's params
    seg = params["decoder"][-1]["e0"]
    layer_moe = jax.tree.map(lambda p: p[0], seg["moe"])
    layer_moe = {k: v for k, v in layer_moe.items() if k != "shared"}
    b, s, d = 2, moe_mod.GROUPWISE_MIN_TOKENS, cfg.d_model
    x = jax.random.normal(jax.random.key(1), (b, s, d), jnp.float32) * 0.3
    y_grouped, _ = moe_mod.moe_ffn(layer_moe, x, cfg, spec)
    yt, _, _ = moe_mod._moe_tokens(layer_moe, x.reshape(b * s, d), cfg)
    y_global = yt.reshape(b, s, d)
    # generous capacity => no drops on either path => identical routing
    np.testing.assert_allclose(np.asarray(y_grouped), np.asarray(y_global),
                               atol=2e-4, rtol=2e-4)


def test_attention_projections_init_at_their_fan_in():
    """wq/wk/wv read d_model inputs and wo reads heads x d_head, so each
    draws at std 1/sqrt(fan_in); at the head count's fan-in the scores'
    std is ~100x too large and attention degenerates to an argmax."""
    cfg = get_config("tiny-agent").replace(d_model=256, n_heads=4,
                                           n_kv_heads=2, d_head=64)
    params = models.init(cfg, jax.random.key(0))
    fan_in = {"wq": 256, "wk": 256, "wv": 256, "wo": 4 * 64}
    seen = set()
    for path, w in jax.tree_util.tree_leaves_with_path(params):
        name = getattr(path[-1], "key", None)
        if name in fan_in:
            seen.add(name)
            std = float(np.asarray(w, np.float32).std())
            assert std == pytest.approx(fan_in[name] ** -0.5, rel=0.05), name
    assert seen == set(fan_in)
