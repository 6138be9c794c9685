"""End-to-end behaviour: federated DCCO pretraining on small non-IID
clients improves representations (paper's headline claim, miniaturized),
and the pod-scale fused train step is gradient-identical to the
protocol-faithful per-client path."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import utils
from repro.configs.base import get_config, DualEncoderConfig, TrainConfig
from repro.core import eval as eval_lib, fed_sim
from repro.data import pipeline, synthetic
from repro.launch import steps as steps_lib
from repro.models import dual_encoder
from repro.optim import optimizers as opt_lib


def _resnet_setup(rng_key):
    cfg = get_config("resnet14-cifar", smoke=True)
    de = DualEncoderConfig(proj_dims=(32, 32), lambda_cco=5.0)
    params = dual_encoder.init_dual_encoder(rng_key, cfg, de)

    def apply(p, batch):
        zf, _ = dual_encoder.encode(cfg, de, p, {"images": batch["v1"]})
        zg, _ = dual_encoder.encode(cfg, de, p, {"images": batch["v2"]})
        return zf, zg

    def embed(p, images):
        from repro.models import resnet as resnet_mod
        return resnet_mod.resnet_forward(cfg, p["tower"], images)

    return cfg, de, params, apply, embed


def test_dcco_pretraining_improves_linear_probe(rng_key):
    """30 rounds of DCCO on non-IID 2-sample clients must beat the
    random-init encoder under the linear evaluation protocol."""
    cfg, de, params, apply, embed = _resnet_setup(rng_key)
    imgs, labels = synthetic.synthetic_labeled_images(
        600, 5, image_size=cfg.image_size, noise=0.5, seed=1)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=128, samples_per_client=2,
        alpha=0.0, seed=0)
    opt = opt_lib.adam(2e-3)
    state = opt.init(params)
    p = params

    def probe(pp):
        z = embed(pp, jnp.asarray(imgs))
        return float(eval_lib.ridge_linear_probe(
            z[:400], jnp.asarray(labels[:400]), z[400:],
            jnp.asarray(labels[400:]), 5))

    acc0 = probe(params)
    losses = []
    for r in range(30):
        batch, sizes = ds.round_batch(jax.random.PRNGKey(100 + r), 16)
        p, state, m = fed_sim.dcco_round(apply, p, state, opt, batch, sizes,
                                         lam=5.0, client_lr=1.0)
        losses.append(float(m.loss))
    acc1 = probe(p)
    assert losses[-1] < losses[0], f"loss did not decrease: {losses[0]} -> {losses[-1]}"
    assert acc1 > acc0 - 0.02, f"probe degraded: {acc0} -> {acc1}"
    assert np.isfinite(losses).all()


def test_fused_step_matches_per_client_step(rng_key):
    """The optimized pod-scale loss path == the faithful per-client path
    (theorem at the train-step level, with a real transformer tower)."""
    cfg = get_config("tinyllama-1.1b", smoke=True)
    de = DualEncoderConfig(proj_dims=(16, 16), lambda_cco=5.0)
    opt = opt_lib.sgd(0.1)
    params = dual_encoder.init_dual_encoder(rng_key, cfg, de)
    toks = jax.random.randint(rng_key, (4, 16), 0, cfg.vocab_size)
    batch = {"view1": {"tokens": toks}, "view2": {"tokens": jnp.roll(toks, 1, -1)}}

    outs = {}
    for impl in ("fused", "per_client"):
        tcfg = TrainConfig(seq_len=16, global_batch=4, samples_per_client=2,
                           dcco_impl=impl)
        step = steps_lib.make_dcco_train_step(cfg, de, tcfg, opt)
        p2, _, m = step(params, opt.init(params), batch)
        outs[impl] = (p2, float(m["loss"]))
    np.testing.assert_allclose(outs["fused"][1], outs["per_client"][1], rtol=1e-5)
    # relative tolerance: the two paths reorder f32 summations, so absolute
    # diffs measure conditioning, not the theorem (cf. TestResNetEquivalence)
    diff = utils.tree_max_abs_diff(outs["fused"][0], outs["per_client"][0])
    upd = utils.tree_max_abs_diff(outs["fused"][0], params) + 1e-12
    assert diff / upd < 1e-4, f"relative deviation {diff / upd}"


def test_lm_train_step_decreases_loss(rng_key):
    cfg = get_config("tinyllama-1.1b", smoke=True)
    opt = opt_lib.adam(1e-3)
    from repro.models import transformer
    params = transformer.init_params(cfg, rng_key)
    step = jax.jit(steps_lib.make_lm_train_step(cfg, opt))
    state = opt.init(params)
    toks = jax.random.randint(rng_key, (4, 32), 0, 64)  # low-entropy slice
    losses = []
    for _ in range(20):
        params, state, m = step(params, state, {"tokens": toks})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9


def test_checkpoint_resume_federated_training(tmp_path, rng_key):
    """Checkpoint mid-training, restore, continue — identical trajectory."""
    import os
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    cfg, de, params, apply, _ = _resnet_setup(rng_key)
    imgs, labels = synthetic.synthetic_labeled_images(100, 4, image_size=cfg.image_size)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=20, samples_per_client=2,
        alpha=0.0, seed=0)
    opt = opt_lib.adam(1e-3)
    state = opt.init(params)
    p = params
    for r in range(2):
        batch, sizes = ds.round_batch(jax.random.PRNGKey(r), 4)
        p, state, _ = fed_sim.dcco_round(apply, p, state, opt, batch, sizes)
    path = os.path.join(tmp_path, "fed.msgpack")
    save_checkpoint(path, {"params": p, "opt": state}, step=2)
    restored, step = restore_checkpoint(path, {"params": p, "opt": state})
    batch, sizes = ds.round_batch(jax.random.PRNGKey(99), 4)
    p_a, _, _ = fed_sim.dcco_round(apply, p, state, opt, batch, sizes)
    p_b, _, _ = fed_sim.dcco_round(apply, restored["params"], restored["opt"],
                                   opt, batch, sizes)
    assert utils.tree_max_abs_diff(utils.tree_cast(p_a, jnp.float32),
                                   utils.tree_cast(p_b, jnp.float32)) < 1e-7


def test_chunked_probe_matches_one_shot_forward(rng_key):
    """The trainer's probe encodes the dataset in chunks (O(chunk) memory)
    and equals one forward over all images, for 4-D images and for the
    dataset's staged (N, H*W*C) rows alike; 70 images in chunks of 32 also
    exercise the padded last chunk."""
    from repro.launch import train
    cfg, _, params, _, embed = _resnet_setup(rng_key)
    imgs, _ = synthetic.synthetic_labeled_images(
        70, 5, image_size=cfg.image_size, noise=0.5, seed=3)
    one_shot = embed(params, jnp.asarray(imgs))
    probe = train.make_probe_features(cfg, chunk=32)
    for x in (imgs, imgs.reshape(len(imgs), -1)):
        np.testing.assert_allclose(np.asarray(probe(params["tower"], x)),
                                   np.asarray(one_shot), rtol=1e-5,
                                   atol=1e-5)


def test_train_main_takes_argv(tmp_path, monkeypatch):
    """``train.main(argv)`` runs the engine in-process (the chip smoke's
    entry point) and returns the per-round losses, the probe, and one wall
    time per scan segment."""
    from repro.launch import train
    # set: JAX read no cache dir at import, so this run caches nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = train.main(["--rounds", "4", "--chunk-rounds", "2",
                      "--eval-every", "2", "--dataset-size", "64",
                      "--clients-per-round", "4",
                      "--ckpt-dir", str(tmp_path / "ck")])
    assert len(out["history"]) == 4
    assert np.isfinite(out["history"]).all()
    assert len(out["segment_seconds"]) == 2
    assert 0.0 <= out["probe"] <= 1.0


def test_compile_cache_placed_from_outside(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set nothing is configured in code;
    unset, the cache goes to one fixed, git-ignored path in the checkout."""
    import os
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(utils.COMPILE_CACHE_ENV, "/elsewhere")
    assert utils.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(utils.COMPILE_CACHE_ENV)
    try:
        assert utils.enable_compile_cache() == utils.REPO_COMPILE_CACHE
        assert jax.config.jax_compilation_cache_dir == \
            utils.REPO_COMPILE_CACHE
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(utils.REPO_COMPILE_CACHE)
    assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(utils.REPO_COMPILE_CACHE) + "/" in \
            f.read().split()
