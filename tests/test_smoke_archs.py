"""Per-architecture smoke tests (assignment deliverable f): a REDUCED
variant of each family runs one forward + one DCCO train step on CPU with
correct shapes and no NaNs, plus prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import utils
from repro.configs.base import ARCH_IDS, TrainConfig, DualEncoderConfig, get_config
from repro.launch import steps as steps_lib
from repro.models import dual_encoder, transformer
from repro.optim import optimizers as opt_lib

TRANSFORMER_ARCHS = [a for a in ARCH_IDS if a != "resnet14-cifar"]
B, S = 2, 32


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    v1 = {"tokens": toks}
    if cfg.modality == "vision_text":
        v2 = {"tokens": toks[:, :1],
              "patch_embeds": jax.random.normal(
                  key, (B, cfg.vis_patches, cfg.vis_dim), jnp.float32)}
    else:
        v2 = {"tokens": jnp.roll(toks, 3, axis=-1)}
    return {"view1": v1, "view2": v2}


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_forward_shapes_no_nan(arch, rng_key):
    cfg = get_config(arch, smoke=True)
    params = transformer.init_params(cfg, rng_key)
    toks = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)
    kw = {}
    if cfg.modality == "vision_text":
        kw["patch_embeds"] = jax.random.normal(rng_key, (B, cfg.vis_patches, cfg.vis_dim))
    h = transformer.forward(cfg, params, toks, **kw)
    exp_s = S + (cfg.vis_patches if cfg.modality == "vision_text" else 0)
    assert h.shape == (B, exp_s, cfg.d_model)
    assert not bool(jnp.isnan(h).any())
    logits = transformer.logits_from_hidden(cfg, params, h[:, -1])
    assert logits.shape == (B, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_dcco_train_step(arch, rng_key):
    cfg = get_config(arch, smoke=True)
    de = DualEncoderConfig(proj_dims=(32, 32), lambda_cco=5.0)
    tcfg = TrainConfig(seq_len=S, global_batch=B, samples_per_client=1)
    opt = opt_lib.adam(1e-3)
    step = steps_lib.make_dcco_train_step(cfg, de, tcfg, opt)
    params = dual_encoder.init_dual_encoder(rng_key, cfg, de)
    opt_state = opt.init(params)
    batch = _batch(cfg, rng_key)
    p2, opt_state, metrics = step(params, opt_state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert not utils.has_nan(p2)
    assert utils.tree_max_abs_diff(p2, params) > 0.0, "params did not update"


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_prefill_decode_consistency(arch, rng_key):
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:
        # avoid capacity-drop divergence between batched and single-token routing
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = transformer.init_params(cfg, rng_key)
    toks = jax.random.randint(rng_key, (B, 16), 0, cfg.vocab_size)
    h = transformer.forward(cfg, params, toks)
    ref = transformer.logits_from_hidden(cfg, params, h[:, -1])
    cache = transformer.init_cache(cfg, B, max_len=20)
    _, cache = transformer.prefill(cfg, params, toks[:, :15], cache)
    ld, cache = transformer.decode_step(cfg, params, cache, toks[:, 15:16])
    scale = float(jnp.max(jnp.abs(ref))) + 1e-6
    assert float(jnp.max(jnp.abs(ref - ld))) < 2e-2 * max(scale, 1.0)
    assert int(cache["pos"]) == 16


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_serve_step_multiple_tokens(arch, rng_key):
    cfg = get_config(arch, smoke=True)
    params = transformer.init_params(cfg, rng_key)
    serve = steps_lib.make_serve_step(cfg)
    cache = transformer.init_cache(cfg, B, max_len=8)
    tok = jax.random.randint(rng_key, (B, 1), 0, cfg.vocab_size)
    for t in range(4):
        logits, cache = serve(params, cache, {"tokens": tok})
        assert logits.shape == (B, cfg.vocab_size)
        assert not bool(jnp.isnan(logits).any())
        tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
    assert int(cache["pos"]) == 4


def test_resnet_smoke(rng_key):
    cfg = get_config("resnet14-cifar", smoke=True)
    de = DualEncoderConfig(proj_dims=(32, 32), lambda_cco=5.0)
    params = dual_encoder.init_dual_encoder(rng_key, cfg, de)
    imgs = jax.random.uniform(rng_key, (4, cfg.image_size, cfg.image_size, 3))
    z, _ = dual_encoder.encode(cfg, de, params, {"images": imgs})
    assert z.shape == (4, 32)
    assert not bool(jnp.isnan(z).any())


@pytest.mark.parametrize("groups", [1, 4, 8])
def test_groupnorm_pools_space_and_group_channels(groups, rng_key):
    """The ResNet's GroupNorm (Wu & He) normalizes each sample's group of
    C/G channels over those channels AND every spatial position — not over
    each pixel's C/G channels alone — and no sample sees another."""
    import numpy as np
    from repro.models.common import groupnorm
    b, h, w, c = 3, 5, 6, 16
    x = jax.random.normal(rng_key, (b, h, w, c)) * 3.0 + 1.0
    scale = jnp.linspace(0.5, 1.5, c)
    bias = jnp.linspace(-1.0, 1.0, c)
    out = np.asarray(groupnorm(x, groups, scale, bias))
    xr = np.asarray(x, np.float64).reshape(b, h, w, groups, c // groups)
    mu = xr.mean(axis=(1, 2, 4), keepdims=True)
    var = xr.var(axis=(1, 2, 4), keepdims=True)
    want = ((xr - mu) / np.sqrt(var + 1e-5)).reshape(b, h, w, c)
    want = want * np.asarray(scale) + np.asarray(bias)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    alone = np.asarray(groupnorm(x[1:2], groups, scale, bias))
    np.testing.assert_allclose(alone, out[1:2], rtol=1e-6, atol=1e-6)
