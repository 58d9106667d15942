"""``correct`` comes out false when the timed path is broken underneath.

Each test drives the rest of a run (set-up, the window, the reference
check) at the configuration's rehearsal sizes on the CPU, with one fault
planted in the program: a token altered where it is sampled, a decode
step that returns its state unchanged, and half of the decode batch
left out.  (The cells run on one chip, so there is no exchange between
chips to leave out.)
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spec

CELLS = [("qwen2-vl-2b", "agent_loop"),
         ("h2o-danube-3-4b", "prefill_heavy")]


def run(cell: tuple, seed: int = 4):
    cell = spec.rehearsal_cell(*cell)
    out, compared = harness.run(cell, seed, 4.0, False, True,
                                time.perf_counter())
    return out, compared


@pytest.fixture(autouse=True)
def _cpu():
    assert jax.default_backend() == "cpu" or os.environ.get(
        "JAX_PLATFORMS") == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out, compared = run(cell)
    assert out["correct"], compared


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token(monkeypatch, cell):
    from repro.serving import sampler
    real = sampler.sample

    def shifted(logits, key, temperature):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampler, "sample", shifted)
    out, compared = run(cell)
    assert not out["correct"], compared


@pytest.mark.parametrize("cell", CELLS)
def test_state_returned_unchanged(monkeypatch, cell):
    from repro import models
    real = models.decode_step

    def stale(params, cfg, tokens, cache, tables=None):
        logits, _ = real(params, cfg, tokens, cache, tables)
        return logits, cache

    monkeypatch.setattr(models, "decode_step", stale)
    out, compared = run(cell)
    assert not out["correct"], compared


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(monkeypatch, cell):
    from repro import models
    real = models.decode_step

    def half(params, cfg, tokens, cache, tables=None):
        logits, new = real(params, cfg, tokens, cache, tables)
        keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.where(keep[:, None], logits, 0.0).astype(logits.dtype), new

    monkeypatch.setattr(models, "decode_step", half)
    out, compared = run(cell)
    assert not out["correct"], compared
