"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates its REDUCED config and runs one forward + one train step
on CPU, asserting output shapes and no NaNs; cache-bearing archs also run a
decode step and check it against the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_params, loss_fn, param_count)
from repro.optim import OptimizerSpec
from repro.train import TrainState, make_train_step

B, S = 2, 32


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :S], "labels": toks[:, 1:],
             "mask": jnp.ones((B, S), jnp.float32)}
    if cfg.family == "encdec":
        dec = min(cfg.decoder_len, S)
        batch["enc_embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                                jnp.float32)
        batch["tokens"] = toks[:, :dec]
        batch["labels"] = toks[:, 1:dec + 1]
        batch["mask"] = jnp.ones((B, dec), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)

    logits, aux = jax.jit(
        lambda p, b: forward(cfg, p, tokens=b["tokens"],
                             enc_embeds=b.get("enc_embeds")))(params, batch)
    T = batch["tokens"].shape[1]
    assert logits.shape == (B, T, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), f"{arch}: NaN logits"

    spec = OptimizerSpec(kind="adamw", lr=1e-3)
    state = TrainState.create(cfg, spec, key)
    step = jax.jit(make_train_step(cfg, spec))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch}: NaN loss"
    assert int(state2.step) == 1
    # params actually changed
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(state2.params)))
    assert d > 0, f"{arch}: optimizer step was a no-op"


@pytest.mark.parametrize("arch", [a for a in ARCHS])
def test_smoke_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    if cfg.family == "encdec":
        pytest.skip("cross-attention decode checked in test_serve")
    # fp32 for a tight comparison
    import dataclasses
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (B, 16), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p, t: forward(cfg, p, tokens=t))(params, toks)
    cache = init_cache(cfg, B, 20)
    step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    outs = []
    for t in range(16):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_full_configs_match_published_sizes():
    expect = {
        "whisper-base": (0.07e9, 0.11e9),
        "qwen2-1.5b": (1.4e9, 1.7e9),
        "deepseek-coder-33b": (32e9, 35e9),
        "gemma3-4b": (3.5e9, 4.5e9),
        "llama3-405b": (400e9, 412e9),
        "zamba2-1.2b": (1.0e9, 1.4e9),
        "mixtral-8x7b": (45e9, 48e9),
        "qwen2-moe-a2.7b": (13e9, 15e9),
        "chameleon-34b": (33e9, 35.5e9),
        "mamba2-370m": (0.3e9, 0.45e9),
        "granite-4.0-h-micro": (3.0e9, 3.4e9),
    }
    for arch in ARCHS:
        n = get_config(arch).n_params()
        lo, hi = expect[arch]
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params():
    mix = get_config("mixtral-8x7b")
    assert 12e9 <= mix.active_params() <= 14e9
    qm = get_config("qwen2-moe-a2.7b")
    assert 2.2e9 <= qm.active_params() <= 3.2e9

