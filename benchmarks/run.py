"""Benchmark harness — one function per paper table/figure + system
microbenchmarks. Prints ``name,us_per_call,derived`` CSV rows.

  table1_cifar    — paper Table 1 protocol, miniaturized: decentralized
                    CIFAR-like splits (clients x samples/client, IID vs
                    non-IID) x {DCCO, CCO+FedAvg, contrastive+FedAvg,
                    centralized CCO, supervised}; derived = linear-probe acc.
  table2_derm     — paper Table 2 protocol: variable 1-6 samples/client
                    (DERM-like), sweep clients/round; derived = probe acc.
  figure3_collapse— paper App. C: BYOL-with-GN collapse probe;
                    derived = encoding std (byol vs cco).
  dcco_round      — federated round latency vs clients/round.
  fused_step      — pod-style fused DCCO step latency (1-device).
  stats_kernel    — fused cco_stats kernel (interpret) vs jnp ref.
  comm_sweep      — bytes-on-the-wire vs probe accuracy across the
                    repro.comm channels (dense / int8 / DP / dropout) on
                    the synthetic non-IID benchmark.
  objective_sweep — the StatsObjective protocol per registered objective
                    (dcco / dvicreg / dwmse): stats payload bytes, kernel
                    time for the objective's moment set, probe accuracy.
  population_scale— cohort size as a memory-free knob (repro.hierarchy):
                    round time + compiled peak memory, materialized vs
                    streamed (cohort_chunk), cohort 64 -> 4096 clients.
  server_opt_sweep— non-IID severity (label-sharded vs IID) x server
                    update strategy (fedavg_sgd / fedavgm / fedadam /
                    fedyogi / fedadam+scaffold), probe accuracy per cell
                    (repro.server).
  retrieval_serving— fused MIPS top-k serving vs the naive materialize-
                    then-top_k program: compiled temp memory (gated),
                    calibrated fraction-of-roofline (gated), QueryServer
                    qps/p50/p99 vs corpus size.
  mixed_precision — EngineConfig.compute_dtype: modeled bf16-vs-f32 step
                    speedup at TPU peaks (gated) + measured probe-accuracy
                    parity on the bench encoder (gated) + measured CPU
                    wall-clock (informational).
  heterogeneity_sweep — client-heterogeneity scenario suite: partition
                    strategy x severity -> cut time + label-dominance
                    skew metric, and clustered (EngineConfig.num_clusters,
                    repro.cluster) vs global aggregation -> cluster-matched
                    probe accuracy (gated: clustered >= global at
                    severity >= 0.8).
  comm_round      — one federated comm round's wall-clock, dense vs int8/
                    int4: measured channel compute + modeled federated-
                    uplink wire time; int8 <= dense is gated HARD.
  kernel_roofline — calibrated fraction-of-roofline for the cco_stats /
                    segment_sum / quantize kernels (gated no-regress).
  roofline        — emits the analytic roofline rows (see roofline.py),
                    including the MIPS serving and federated-kernel shapes.

Set ``BENCH_SMOKE=1`` to shrink the timed sweeps to CI-smoke sizes (the
bench-regression gate in CI runs ``round_engine`` + ``comm_sweep`` +
``objective_sweep`` + ``stats_kernel`` + ``population_scale`` +
``retrieval_serving`` this way and compares against
benchmarks/baseline.json via compare.py).

All model-scale numbers are CPU-host timings of reduced configs — relative
comparisons only; absolute TPU numbers come from the §Roofline analysis.

Besides the printed CSV, every run dumps its rows as machine-readable JSON
(default ``BENCH.json`` in the working directory; override with the
``BENCH_JSON`` env var) so the perf trajectory is diffable across PRs.
Pass benchmark names as argv to run a subset: ``python benchmarks/run.py
comm_sweep round_engine``.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import roofline as roofline_mod
from repro import comm
from repro.configs.base import DualEncoderConfig, get_config
from repro.core import cco, eval as eval_lib, fed_sim, losses, round_engine
from repro.data import pipeline, synthetic
from repro.models import dual_encoder, resnet as resnet_mod
from repro.optim import optimizers as opt_lib

ROWS = []


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append({"name": name, "us_per_call": round(us_per_call, 1),
                 "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn, n=3, best_of=1):
    """Mean us/call over n calls; with best_of > 1, the MINIMUM of best_of
    such batch means. Best-of is the noise-robust choice for the
    calibrated roofline fractions: a scheduler stall inflates a mean
    forever, but the min converges to the machine's actual capability —
    and the fraction divides two timings, so it carries both their
    noise."""
    out = fn()  # warmup/compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(best_of):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e6


def _calibrate_peaks(seed=0, mm_dim=1024):
    """Measure THIS machine's achievable peaks in-process: a jitted
    (mm_dim, mm_dim) matmul for flops/s and a 64 MB f32 elementwise copy
    for HBM bytes/s. Every calibrated fraction-of-roofline row
    (`retrieval_serving`, `kernel_roofline`) scores a measured kernel time
    against an analytic bound evaluated at THESE peaks, so the fraction is
    a ratio of two same-process measurements — portable across runner
    generations, unlike absolute us. Best-of-timed (see _timeit) so a
    transiently loaded runner shrinks neither peak. Returns
    (flops_per_s, bytes_per_s).
    """
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (mm_dim, mm_dim), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (mm_dim, mm_dim),
                          jnp.float32)
    matmul = jax.jit(lambda a, b: a @ b)
    flops_s = 2.0 * mm_dim ** 3 / (
        _timeit(lambda: matmul(a, b), n=5, best_of=4) / 1e6)
    big = jnp.zeros((16, 1 << 20), jnp.float32)          # 64 MB
    copy = jax.jit(lambda x: x * 1.0000001)
    bytes_s = 2.0 * big.nbytes / (
        _timeit(lambda: copy(big), n=5, best_of=4) / 1e6)
    return flops_s, bytes_s


# ---------------------------------------------------------------------------
# shared miniature CIFAR-like setup (paper Sec 4.1-4.3, reduced)
# ---------------------------------------------------------------------------

def _setup(seed=0):
    cfg = get_config("resnet14-cifar", smoke=True)
    de = DualEncoderConfig(proj_dims=(64, 64), lambda_cco=5.0)
    key = jax.random.PRNGKey(seed)
    params = dual_encoder.init_dual_encoder(key, cfg, de)

    def apply(p, batch):
        zf, _ = dual_encoder.encode(cfg, de, p, {"images": batch["v1"]})
        zg, _ = dual_encoder.encode(cfg, de, p, {"images": batch["v2"]})
        return zf, zg

    def embed(p, images):
        return resnet_mod.resnet_forward(cfg, p["tower"], images)

    return cfg, de, params, apply, embed


def _probe(embed, params, imgs, labels, n_train=400):
    z = embed(params, jnp.asarray(imgs))
    return float(eval_lib.ridge_linear_probe(
        z[:n_train], jnp.asarray(labels[:n_train]),
        z[n_train:], jnp.asarray(labels[n_train:]), int(labels.max()) + 1))


def _make_round_fn(method, apply, opt):
    """jit once per (method, shapes) — eager vmapped rounds are ~20x slower."""
    if method == "dcco":
        def fn(p, st, batch, sizes):
            return fed_sim.dcco_round(apply, p, st, opt, batch, sizes,
                                      lam=5.0, client_lr=1.0)
    elif method == "cco_fedavg":
        def fn(p, st, batch, sizes):
            return fed_sim.fedavg_round(apply, p, st, opt, batch, sizes,
                                        loss_kind="cco", lam=5.0, client_lr=0.5)
    elif method == "contrastive_fedavg":
        def fn(p, st, batch, sizes):
            return fed_sim.fedavg_round(apply, p, st, opt, batch, sizes,
                                        loss_kind="contrastive", client_lr=0.5)
    elif method == "centralized":
        def fn(p, st, batch, sizes):
            union = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
            mask = (jnp.arange(batch["v1"].shape[1])[None]
                    < sizes[:, None]).reshape(-1).astype(jnp.float32)
            return fed_sim.centralized_step(apply, p, st, opt, union,
                                            mask=mask, lam=5.0)
    else:
        raise ValueError(method)
    return jax.jit(fn)


def _pretrain(method, params, apply, ds, rounds, clients_per_round, opt_lr=2e-3):
    opt = opt_lib.adam(opt_lr)
    state = opt.init(params)
    p = params
    m = None
    round_fn = _make_round_fn(method, apply, opt)
    t0 = time.perf_counter()
    for r in range(rounds):
        batch, sizes = ds.round_batch(jax.random.PRNGKey(1000 + r),
                                      clients_per_round)
        p, state, m = round_fn(p, state, batch, sizes)
    us = (time.perf_counter() - t0) / rounds * 1e6
    return p, us, float(m.loss)


def table1_cifar(rounds=25):
    """Paper Table 1, miniaturized: split x method -> probe accuracy."""
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=0.5, seed=1)
    cfg, de, params0, apply, embed = _setup()
    acc_rand = _probe(embed, params0, imgs, labels)
    emit("table1/random_init_probe", 0.0, f"acc={acc_rand:.3f}")
    splits = [("noniid_s1", 0.0, 1, 32), ("noniid_s4", 0.0, 4, 8),
              ("iid_s4", 1e9, 4, 8)]
    for split_name, alpha, spc, cpr in splits:
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=256 // max(spc, 1),
            samples_per_client=spc, alpha=alpha, seed=0)
        for method in ("dcco", "cco_fedavg", "contrastive_fedavg", "centralized"):
            if method == "cco_fedavg" and spc < 2:
                emit(f"table1/{split_name}/{method}", 0.0,
                     "acc=FAILED(n<2, per paper)")
                continue
            p, us, loss = _pretrain(method, params0, apply, ds, rounds, cpr)
            acc = _probe(embed, p, imgs, labels)
            emit(f"table1/{split_name}/{method}", us,
                 f"acc={acc:.3f};loss={loss:.3f}")
    sup = _supervised_scratch(cfg, imgs, labels)
    emit("table1/supervised_scratch", 0.0, f"acc={sup:.3f}")


def _supervised_scratch(cfg, imgs, labels, steps=60):
    key = jax.random.PRNGKey(3)
    n_cls = int(labels.max()) + 1
    p = {"tower": resnet_mod.resnet_init(key, cfg, jnp.float32),
         "head": {"w": jnp.zeros((cfg.d_model, n_cls)), "b": jnp.zeros((n_cls,))}}
    opt = opt_lib.adam(5e-3)
    st = opt.init(p)
    # limited labeled data (paper: 1-10% of the dataset; we use ~7%)
    x_tr = jnp.asarray(imgs[:40])
    y_tr = jnp.asarray(labels[:40])

    @jax.jit
    def step(p, st):
        def loss_fn(pp):
            z = resnet_mod.resnet_forward(cfg, pp["tower"], x_tr)
            logits = z @ pp["head"]["w"] + pp["head"]["b"]
            return losses.softmax_cross_entropy(logits, y_tr)
        g = jax.grad(loss_fn)(p)
        upd, st2 = opt.update(g, st, p)
        return opt_lib.apply_updates(p, upd), st2

    for _ in range(steps):
        p, st = step(p, st)
    z = resnet_mod.resnet_forward(cfg, p["tower"], jnp.asarray(imgs[400:]))
    logits = z @ p["head"]["w"] + p["head"]["b"]
    return float((jnp.argmax(logits, -1) == jnp.asarray(labels[400:])).mean())


def table2_derm(rounds=25):
    """Paper Table 2 protocol: clients hold 1-6 images; sweep clients/round."""
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=0.5, seed=2)
    cfg, de, params0, apply, embed = _setup(seed=1)
    rng = np.random.RandomState(0)
    n_pad = 6
    num_clients = 80
    idx = rng.permutation(600)[: num_clients * n_pad].reshape(num_clients, n_pad)
    ds = pipeline.FederatedDataset({"images": imgs}, labels, idx)

    for cpr in (8, 16, 32):
        for method in ("dcco", "contrastive_fedavg"):
            opt = opt_lib.adam(2e-3)
            state = opt.init(params0)
            p = params0
            srng = np.random.RandomState(7)
            round_fn = _make_round_fn(method, apply, opt)
            t0 = time.perf_counter()
            for r in range(rounds):
                batch, _ = ds.round_batch(jax.random.PRNGKey(r), cpr)
                sizes = jnp.asarray(srng.randint(1, n_pad + 1, cpr), jnp.int32)
                p, state, m = round_fn(p, state, batch, sizes)
            us = (time.perf_counter() - t0) / rounds * 1e6
            acc = _probe(embed, p, imgs, labels)
            emit(f"table2/cpr{cpr}/{method}", us, f"acc={acc:.3f}")
    emit("table2/cco_fedavg", 0.0, "acc=FAILED(unstable n<=6, per paper)")


def figure3_collapse():
    """App. C / footnote 1 as a landscape probe: the constant (collapsed)
    encoder is the predictive loss's global minimum — 'loss drops to its
    lowest possible value' — while the CCO loss there is large and a
    whitened encoder beats it by >10x (collapse is not a CCO solution)."""
    key = jax.random.PRNGKey(0)
    n, d = 64, 8
    z_const = jnp.ones((n, d)) * 0.7 + 1e-4 * jax.random.normal(key, (n, d))
    byol_c = float(losses.byol_predictive_loss(z_const, z_const))
    cco_c = float(cco.cco_loss(z_const, z_const, 5.0))
    zf = jax.random.normal(jax.random.PRNGKey(1), (4096, d))
    zc = zf - zf.mean(0)
    u, s_, vt = jnp.linalg.svd(zc, full_matrices=False)
    zw = u * jnp.sqrt(4096)
    cco_w = float(cco.cco_loss(zw, zw, 5.0))
    emit("figure3/predictive_loss_at_collapse", 0.0,
         f"loss={byol_c:.2e}(global_min)")
    emit("figure3/cco_loss_at_collapse", 0.0, f"loss={cco_c:.3f}")
    emit("figure3/cco_loss_whitened", 0.0,
         f"loss={cco_w:.4f};collapse_penalty={cco_c / max(cco_w, 1e-6):.0f}x")


def dcco_round_bench():
    cfg, de, params, apply, _ = _setup()
    imgs, labels = synthetic.synthetic_labeled_images(400, 5, image_size=16)
    opt = opt_lib.adam(1e-3)
    state = opt.init(params)
    for cpr in (8, 32):
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=100, samples_per_client=2,
            alpha=0.0, seed=0)
        batch, sizes = ds.round_batch(jax.random.PRNGKey(0), cpr)
        rounder = jax.jit(lambda p, s, b, sz: fed_sim.dcco_round(
            apply, p, s, opt, b, sz, lam=5.0))
        us = _timeit(lambda: rounder(params, state, batch, sizes))
        emit(f"dcco_round/clients{cpr}", us, f"samples={cpr * 2}")


def round_engine_bench(rounds=100, cpr=16):
    """Scan-compiled engine vs the Python round loop, equal rounds.

    The loop path is the pre-engine driver: host-side cohort sampling +
    one jitted round per Python dispatch. The engine compiles sampling and
    all rounds into a single lax.scan program with a donated carry. Measured
    in the paper's regime — tiny clients (s=2), small dual encoder — where
    federated training is dispatch/sampling-bound, the regime the engine
    targets. (A compute-bound body like the smoke ResNet hides dispatch
    under ~90ms of conv work per round; see docs/architecture.md.)"""
    from repro.core import fed_sim, round_engine
    imgs, labels = synthetic.synthetic_labeled_images(400, 5, image_size=16)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=100, samples_per_client=2,
        alpha=0.0, seed=0)
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (16 * 16 * 3, 128)) * 0.05,
              "w2": jax.random.normal(jax.random.PRNGKey(7), (128, 64)) * 0.1}

    def apply(p, batch):
        def enc(x):
            return jnp.tanh(x.reshape(x.shape[0], -1) @ p["w1"]) @ p["w2"]
        return enc(batch["v1"]), enc(batch["v2"])

    opt = opt_lib.adam(1e-3)
    round_fn = jax.jit(lambda p, st, b, s: fed_sim.dcco_round(
        apply, p, st, opt, b, s, lam=5.0))
    batch, sizes = ds.round_batch(jax.random.PRNGKey(0), cpr)
    jax.block_until_ready(round_fn(params, opt.init(params), batch, sizes)[2].loss)
    p, st = params, opt.init(params)
    t0 = time.perf_counter()
    for r in range(rounds):
        batch, sizes = ds.round_batch(jax.random.PRNGKey(1000 + r), cpr)
        p, st, m = round_fn(p, st, batch, sizes)
    jax.block_until_ready(m.loss)
    us_loop = (time.perf_counter() - t0) / rounds * 1e6

    ecfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                     chunk_rounds=rounds)
    eng = round_engine.RoundEngine(apply, opt, ds.make_round_sampler(cpr), ecfg)
    out = eng.run(params, opt.init(params), jax.random.PRNGKey(7), rounds)
    jax.block_until_ready(out[2].loss)                       # warmup/compile
    t0 = time.perf_counter()
    pe, se, me = eng.run(params, opt.init(params), jax.random.PRNGKey(7), rounds)
    jax.block_until_ready(me.loss)
    us_eng = (time.perf_counter() - t0) / rounds * 1e6

    emit("round_engine/python_loop", us_loop, f"rounds={rounds}")
    emit("round_engine/scan_engine", us_eng,
         f"rounds={rounds};speedup={us_loop / us_eng:.2f}x;"
         f"loss={float(me.loss[-1]):.3f}")


def async_stragglers(ticks=60, cpr=16, async_k=4, tail=0.7):
    """Sync vs buffered (FedBuff-style) engine under heavy-tail stragglers.

    Simulated-time model, machine-portable by construction: one scheduler
    tick is the unit of client latency. The SYNC engine waits for its
    slowest sampled client, so a round costs ``1 + max(cohort delays)``
    ticks (delays replayed host-side from the engine's own key stream);
    the BUFFERED engine (EngineConfig.async_k) dispatches a cohort every
    tick and applies an update per K-trigger, so its cost is
    ``ticks / updates_applied`` ticks per update. Both are deterministic
    functions of the latency model and seed — the gated speedup
    (sync/buffered ticks-per-update, benchmarks/compare.py) cancels
    machine speed entirely. Wall-clock us/tick and probe accuracy ride
    along as informational rows.
    """
    from repro.core import round_engine
    from repro.data import latency as latency_lib
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=128, samples_per_client=2,
        alpha=0.0, seed=0)
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (16 * 16 * 3, 128)) * 0.05,
              "w2": jax.random.normal(jax.random.PRNGKey(7), (128, 64)) * 0.1}

    def apply(p, batch):
        def enc(x):
            return jnp.tanh(x.reshape(x.shape[0], -1) @ p["w1"]) @ p["w2"]
        return enc(batch["v1"]), enc(batch["v2"])

    def embed(p, images):
        x = images.reshape(images.shape[0], -1)
        return jnp.tanh(x @ p["w1"]) @ p["w2"]

    lat = latency_lib.LatencyModel("heavytail", horizon=8, tail=tail, seed=0)
    rng = jax.random.PRNGKey(7)

    # simulated sync cost: replay the engine's key derivation (round key =
    # fold_in(rng, r); selection key = split()[0]; delay key = the sampler's
    # fold_in salt) and charge each round its slowest sampled client
    sync_ticks = 0
    for r in range(ticks):
        k_sel, _ = jax.random.split(jax.random.fold_in(rng, r))
        sel = jax.random.choice(k_sel, ds.num_clients, (cpr,), replace=False)
        d = latency_lib.sample_delays(
            lat, jax.random.fold_in(k_sel, latency_lib._LATENCY_SALT),
            sel.astype(jnp.int32))
        sync_ticks += 1 + int(d.max())

    opt = opt_lib.adam(1e-3)
    eng = round_engine.RoundEngine(
        apply, opt, ds.make_round_sampler(cpr),
        round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                  chunk_rounds=ticks))
    out = eng.run(params, opt.init(params), rng, ticks)
    jax.block_until_ready(out[2].loss)                       # warmup/compile
    t0 = time.perf_counter()
    ps, _, ms = eng.run(params, opt.init(params), rng, ticks)
    jax.block_until_ready(ms.loss)
    us_sync = (time.perf_counter() - t0) / ticks * 1e6
    sync_tpu = sync_ticks / ticks
    emit("async_stragglers/sync", us_sync,
         f"ticks={ticks};sim_ticks={sync_ticks};"
         f"probe={_probe(embed, ps, imgs, labels):.3f}")

    eng = round_engine.RoundEngine(
        apply, opt, ds.make_async_round_sampler(cpr, lat),
        round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                  chunk_rounds=ticks, async_k=async_k,
                                  staleness_fn="poly", latency=lat))
    out = eng.run(params, opt.init(params), rng, ticks)
    jax.block_until_ready(out[2].loss)                       # warmup/compile
    t0 = time.perf_counter()
    pb, _, mb = eng.run(params, opt.init(params), rng, ticks)
    jax.block_until_ready(mb.loss)
    us_buf = (time.perf_counter() - t0) / ticks * 1e6
    updates = int(jnp.sum(mb.applied))
    stale = mb.staleness[mb.applied > 0]
    buf_tpu = ticks / max(updates, 1)
    emit("async_stragglers/buffered", us_buf,
         f"ticks={ticks};K={async_k};updates={updates};"
         f"stale={float(stale.mean()) if updates else 0.0:.2f};"
         f"probe={_probe(embed, pb, imgs, labels):.3f}")

    # the gated pair: simulated ticks per server update, sync vs buffered
    emit("async_stragglers/sync_ticks_per_update", sync_tpu,
         f"tail={tail};horizon=8")
    emit("async_stragglers/buffered_ticks_per_update", buf_tpu,
         f"tail={tail};K={async_k};"
         f"speedup={sync_tpu / buf_tpu:.2f}x")


def comm_sweep(rounds=25, cpr=16):
    """Bytes-on-the-wire vs probe accuracy across communication channels.

    Same synthetic non-IID setup as table1 (2-sample single-class clients),
    trained with the scan-compiled engine; every channel sees the identical
    round/cohort stream (the channel key is a fold_in off the round key, so
    selection/augmentation streams match the dense run). The derived column
    reports per-round phase-1 statistics bytes, total uplink MB, and the
    compression ratio vs dense — int8 stats compress ~3.97x (4 bytes -> 1
    byte + one f32 scale per tensor per client).
    """
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=0.5, seed=1)
    cfg, de, params0, apply, embed = _setup()
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=128, samples_per_client=2,
        alpha=0.0, seed=0)
    sampler = ds.make_round_sampler(cpr)
    # per-client phase-1 payload: the CCO objective's stat spec at the
    # bench encoder's projection dim (stays truthful if either changes)
    from repro import objectives as objectives_lib
    stats_tmpl = objectives_lib.get_objective("dcco").stat_template(
        de.proj_dims[-1])
    dense_stats_b = comm.DenseChannel().payload_bytes(stats_tmpl)

    channels = [
        ("dense", comm.DenseChannel()),
        ("int8", comm.QuantizedChannel(8)),
        ("int4", comm.QuantizedChannel(4)),
        ("dp_s0.3", comm.DPGaussianChannel(0.3, clip_norm=10.0)),
        ("dropout_0.3", comm.DropoutChannel(0.3)),
    ]
    acc_dense = None
    for name, ch in channels:
        opt = opt_lib.adam(2e-3)
        ecfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                         chunk_rounds=rounds, channel=ch)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        # warmup run: compiles the scan segment AND produces the trained
        # params for the probe; the timed run below re-runs the identical
        # stream so per-round us is steady-state, not compile-dominated
        # (pre-PR-8 this bench had no warmup, which is why the baseline
        # showed quantized rounds 1.5-1.6x slower than dense — that gap
        # was threefry compile time, not channel compute; the wall-clock
        # comm gate lives in `comm_round`)
        p, _, m = eng.run(params0, opt.init(params0),
                          jax.random.PRNGKey(7), rounds)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        p2, _, _ = eng.run(params0, opt.init(params0),
                           jax.random.PRNGKey(7), rounds)
        jax.block_until_ready(p2)
        us = (time.perf_counter() - t0) / rounds * 1e6
        acc = _probe(embed, p, imgs, labels)
        if acc_dense is None:
            acc_dense = acc
        stats_b = ch.payload_bytes(stats_tmpl)
        total_mb = float(jnp.sum(m.wire_bytes)) / 1e6
        extras = ""
        acct = getattr(ch, "accountant", None)
        if acct is not None:
            extras = f";eps={acct.epsilon():.1f}"
        emit(f"comm_sweep/{name}", us,
             f"acc={acc:.3f};d_acc={acc - acc_dense:+.3f};"
             f"stats_B={stats_b:.0f};stats_ratio={dense_stats_b / stats_b:.2f}x;"
             f"uplink_MB={total_mb:.2f}{extras}")


def server_opt_sweep(rounds=25, cpr=16):
    """Non-IID severity x server-update strategy -> probe accuracy.

    The paper's degradation axis (Table 1): label-sharded single-class
    2-sample clients (alpha=0, the hard setting) vs IID splits of the same
    data. Each cell trains the same DCCO engine run, differing only in the
    repro.server ServerUpdate strategy (and drift correction for the
    scaffold row) — the sweep that motivates server adaptivity on small
    non-IID cohorts. Rows emit probe accuracy and the per-round latency,
    so BENCH.json records both the quality and the cost trajectory.
    """
    from repro.server import get_server_update
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=1.0, seed=1)
    cfg, de, params0, apply, embed = _setup()
    strategies = [
        ("fedavg_sgd", lambda: get_server_update("fedavg_sgd", server_lr=1.0),
         {}),
        ("fedavgm", lambda: get_server_update("fedavgm", server_lr=0.3), {}),
        ("fedadam", lambda: get_server_update("fedadam", server_lr=1e-2), {}),
        ("fedyogi", lambda: get_server_update("fedyogi", server_lr=1e-2), {}),
        # scaffold at one local step: under cohort sampling the slot
        # variates still reshape the update (slot != client), and the
        # 2-sample clients' local stats make multi-step local training
        # diverge regardless of strategy (degenerate within-client
        # variance), so L=1 is the stable comparison point here
        ("fedadam_scaffold",
         lambda: get_server_update("fedadam", server_lr=1e-2),
         {"scaffold": True}),
    ]
    for split_name, alpha in (("noniid", 0.0), ("iid", 1e9)):
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=300, samples_per_client=2,
            alpha=alpha, seed=0)
        sampler = ds.make_round_sampler(cpr)
        acc_base = None
        for name, make_su, extra in strategies:
            su = make_su()
            ecfg = round_engine.EngineConfig(
                algorithm="dcco", lam=5.0, chunk_rounds=rounds,
                server_update=su, **extra)
            eng = round_engine.RoundEngine(apply, su, sampler, ecfg)
            t0 = time.perf_counter()
            p, _, m = eng.run(params0, su.init(params0),
                              jax.random.PRNGKey(7), rounds)
            us = (time.perf_counter() - t0) / rounds * 1e6
            acc = _probe(embed, p, imgs, labels)
            if acc_base is None:
                acc_base = acc
            emit(f"server_opt_sweep/{split_name}/{name}", us,
                 f"acc={acc:.3f};d_acc={acc - acc_base:+.3f};"
                 f"loss={float(m.loss[-1]):.3f}")


def population_scale(rounds=3, cohorts=(64, 256, 1024, 4096), chunk=64,
                     materialize_max=256):
    """Cohort size as a memory-free knob: round time and compiled peak
    memory, materialized vs streamed (EngineConfig.cohort_chunk), as the
    cohort grows 64 -> 4096 clients/round — the cross-device population
    regime (thousands of devices, 2 samples each).

    Same dispatch-bound tiny-encoder setup as ``round_engine_bench``.
    Memory is read from XLA's compiled-program analysis of the engine's
    scan segment (argument + temp bytes — machine-independent, it is the
    compiler's own allocation plan): the materialized path grows O(cohort)
    while the streamed path stays O(chunk). Rows at the largest cohort
    both paths run feed the CI gate in compare.py: the streamed round's
    time overhead over the materialized round (same process, same host,
    so the ratio is machine-portable) must not regress.
    """
    from repro.core import round_engine
    max_cohort = max(cohorts)
    imgs, labels = synthetic.synthetic_labeled_images(
        2 * max_cohort, 5, image_size=16, noise=0.5, seed=0)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=max_cohort,
        samples_per_client=2, alpha=0.0, seed=0)
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (16 * 16 * 3, 128)) * 0.05,
              "w2": jax.random.normal(jax.random.PRNGKey(7), (128, 64)) * 0.1}

    def apply(p, batch):
        def enc(x):
            return jnp.tanh(x.reshape(x.shape[0], -1) @ p["w1"]) @ p["w2"]
        return enc(batch["v1"]), enc(batch["v2"])

    def compiled_bytes(eng, carry):
        """XLA's own allocation plan for one scan segment (bytes). The
        AOT-lowering surface is version-sensitive; a failure degrades the
        row to compiled_MB=0.0 but says so on stderr rather than letting
        the memory evidence vanish silently."""
        try:
            mem = eng._segment_fn(eng.config.chunk_rounds).lower(
                carry, jnp.asarray(0, jnp.int32),
                eng.sampler).compile().memory_analysis()
            return sum(int(getattr(mem, f, 0) or 0) for f in
                       ("argument_size_in_bytes", "temp_size_in_bytes",
                        "output_size_in_bytes"))
        except Exception as e:  # pragma: no cover - jax-version drift
            print(f"population_scale: compiled memory analysis "
                  f"unavailable ({type(e).__name__}: {e}); emitting "
                  f"compiled_MB=0.0", file=sys.stderr)
            return 0

    def run_engine(cohort, cohort_chunk):
        opt = opt_lib.adam(1e-3)
        if cohort_chunk:
            sampler = ds.make_streaming_sampler(cohort, cohort_chunk)
        else:
            sampler = ds.make_round_sampler(cohort)
        ecfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                         chunk_rounds=rounds,
                                         cohort_chunk=cohort_chunk)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        carry = round_engine.EngineCarry(params, opt.init(params),
                                         jax.random.PRNGKey(7))
        mem = compiled_bytes(eng, carry)
        out = eng.run(params, opt.init(params), jax.random.PRNGKey(7), rounds)
        jax.block_until_ready(out[2].loss)            # warmup/compile
        t0 = time.perf_counter()
        p, s, m = eng.run(params, opt.init(params), jax.random.PRNGKey(7),
                          rounds)
        jax.block_until_ready(m.loss)
        us = (time.perf_counter() - t0) / rounds * 1e6
        return us, mem, float(m.loss[-1])

    last_mat = None
    for cohort in cohorts:
        if cohort <= materialize_max:
            us_m, mem_m, _ = run_engine(cohort, 0)
            emit(f"population_scale/materialized_c{cohort}", us_m,
                 f"cohort={cohort};compiled_MB={mem_m / 1e6:.1f}")
            last_mat = (cohort, us_m)
        us_s, mem_s, loss = run_engine(cohort, min(chunk, cohort))
        extra = ""
        if last_mat is not None and last_mat[0] == cohort:
            extra = f";stream_vs_mat={us_s / last_mat[1]:.2f}x"
        emit(f"population_scale/streaming_c{cohort}", us_s,
             f"cohort={cohort};chunk={min(chunk, cohort)};"
             f"compiled_MB={mem_s / 1e6:.1f};loss={loss:.3f}{extra}")


def fused_step_bench():
    from repro.configs.base import TrainConfig
    from repro.launch import steps as steps_lib
    cfg = get_config("tinyllama-1.1b", smoke=True)
    de = DualEncoderConfig(proj_dims=(64, 64), lambda_cco=5.0)
    opt = opt_lib.adam(1e-3)
    key = jax.random.PRNGKey(0)
    params = dual_encoder.init_dual_encoder(key, cfg, de)
    toks = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
    batch = {"view1": {"tokens": toks}, "view2": {"tokens": jnp.roll(toks, 1, -1)}}
    for nm in (1, 4):
        tcfg = TrainConfig(seq_len=64, global_batch=8, dcco_impl="fused")
        step = jax.jit(steps_lib.make_dcco_train_step(
            cfg, de, tcfg, opt, num_microbatches=nm))
        st = opt.init(params)
        us = _timeit(lambda: step(params, st, batch))
        emit(f"fused_step/micro{nm}", us,
             "exact_microbatch" if nm > 1 else "plain")


def stats_kernel_bench(sizes=((512, 256), (2048, 512))):
    from repro.kernels import ref
    from repro.kernels.cco_stats import cco_stats_pallas
    key = jax.random.PRNGKey(0)
    for (n, d) in sizes:
        zf = jax.random.normal(key, (n, d))
        zg = jax.random.normal(jax.random.PRNGKey(1), (n, d))
        us_k = _timeit(lambda: cco_stats_pallas(zf, zg, interpret=True), n=1)
        us_f = _timeit(lambda: cco_stats_pallas(zf, zg, interpret=True,
                                                moments="full"), n=1)
        us_r = _timeit(lambda: ref.cco_stats_ref(zf, zg))
        naive = 5 * 2 * n * d * 4            # five separate passes
        fused = 2 * n * d * 4 + d * d * 4    # one pass + output
        emit(f"stats_kernel/{n}x{d}", us_k,
             f"ref_us={us_r:.0f};full_moments_us={us_f:.0f};"
             f"full_vs_cross={us_f / us_k:.2f}x;"
             f"hbm_naive_vs_fused={naive / fused:.2f}x")

    # The CI-gated row pair (benchmarks/compare.py): the generalized
    # one-pass formulation (all 7 statistics from one read of zf/zg, the
    # computation the Pallas kernel fuses) vs the naive per-statistic
    # passes (one jitted reduction each — 7 separate reads). Both sides
    # run on the same machine in the same process, so the ratio cancels
    # machine speed and isolates what this repo controls: that the fused
    # moment computation stays a single-pass win.
    n, d = 4096, 128
    zf = jax.random.normal(key, (n, d))
    zg = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    one_pass = jax.jit(lambda f, g: ref.cco_stats_ref(f, g,
                                                      second_moments=True))
    naive_fns = [jax.jit(f) for f in (
        lambda f, g: f.mean(0),
        lambda f, g: (f * f).mean(0),
        lambda f, g: g.mean(0),
        lambda f, g: (g * g).mean(0),
        lambda f, g: f.T @ g / f.shape[0],
        lambda f, g: f.T @ f / f.shape[0],
        lambda f, g: g.T @ g / g.shape[0],
    )]
    us_one = _timeit(lambda: one_pass(zf, zg), n=10)
    us_naive = _timeit(lambda: [f(zf, zg) for f in naive_fns], n=10)
    emit("stats_kernel/naive_passes", us_naive, f"n={n};d={d};stats=7")
    emit("stats_kernel/one_pass", us_one,
         f"n={n};d={d};one_pass_vs_naive={us_naive / us_one:.2f}x")


def objective_sweep(rounds=25, cpr=16):
    """The StatsObjective protocol, measured per registered objective:
    phase-1 stats payload bytes, fused-kernel time for the objective's
    moment set (interpret mode — relative cross-vs-full comparison), and
    linear-probe accuracy after the same engine-compiled training run.
    Every objective sees the identical cohort/augmentation stream and a
    DenseChannel wire, so bytes and accuracy are directly comparable.
    """
    from repro import objectives as objectives_lib
    from repro.kernels.cco_stats import cco_stats_pallas
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=0.5, seed=1)
    cfg, de, params0, apply, embed = _setup()
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=128, samples_per_client=2,
        alpha=0.0, seed=0)
    sampler = ds.make_round_sampler(cpr)
    d_enc = de.proj_dims[-1]
    kn, kd = 256, 64
    kzf = jax.random.normal(jax.random.PRNGKey(2), (kn, kd))
    kzg = jax.random.normal(jax.random.PRNGKey(3), (kn, kd))
    for name in objectives_lib.OBJECTIVES:
        obj = objectives_lib.get_objective(
            name, **({"lam": 5.0} if name == "dcco" else {}))
        ch = comm.DenseChannel()
        opt = opt_lib.adam(2e-3)
        ecfg = round_engine.EngineConfig(algorithm="dcco", objective=obj,
                                         chunk_rounds=rounds, channel=ch)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        t0 = time.perf_counter()
        p, _, m = eng.run(params0, opt.init(params0),
                          jax.random.PRNGKey(7), rounds)
        us = (time.perf_counter() - t0) / rounds * 1e6
        acc = _probe(embed, p, imgs, labels)
        stats_b = ch.payload_bytes(obj.stat_template(d_enc))
        moments = "full" if obj.second_moments else "cross"
        us_kernel = _timeit(lambda: cco_stats_pallas(
            kzf, kzg, interpret=True, moments=moments), n=1)
        emit(f"objective_sweep/{name}", us,
             f"acc={acc:.3f};loss={float(m.loss[-1]):.3f};"
             f"stats_B={stats_b:.0f};stats={len(obj.stat_keys)};"
             f"kernel_us={us_kernel:.0f};"
             f"uplink_MB={float(jnp.sum(m.wire_bytes)) / 1e6:.2f}")


def stale_stats_study(rounds=20):
    """Paper Sec. 6 open question: with >1 local steps per round the
    aggregated statistics go stale and gradients are partial. We fix the
    per-round client lr budget C (so first-order effects cancel between
    L steps of lr C/L and 1 step of lr C) and measure the deviation of the
    resulting round update — the pure staleness error. Finding: the
    deviation is O(C) relative (second-order absolute), i.e. multiple local
    steps are safe at small client lrs and increasingly biased at large
    ones; derived column reports dev/|update| per (C, L)."""
    from repro import utils
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.4}

    def apply(p, b):
        return jnp.tanh(b["v1"] @ p["w"]), jnp.tanh(b["v2"] @ p["w"])

    k1, k2 = jax.random.split(key)
    data = {"v1": jax.random.normal(k1, (8, 2, 8)),
            "v2": jax.random.normal(k2, (8, 2, 8))}
    sizes = jnp.full((8,), 2, jnp.int32)
    opt = opt_lib.sgd(1.0)
    for C in (0.1, 0.01):
        ref, _, _ = fed_sim.dcco_round(apply, params, opt.init(params), opt,
                                       data, sizes, lam=5.0, client_lr=C,
                                       local_steps=1)
        upd = utils.tree_norm(utils.tree_sub(ref, params)) + 1e-12
        for L in (2, 4):
            pl, _, _ = fed_sim.dcco_round(apply, params, opt.init(params),
                                          opt, data, sizes, lam=5.0,
                                          client_lr=C / L, local_steps=L)
            dev = utils.tree_norm(utils.tree_sub(pl, ref))
            emit(f"stale_stats/C{C}/L{L}", 0.0,
                 f"rel_dev={float(dev / upd):.5f}")


def dvicreg_bench(rounds=20):
    """Paper Sec. 6 future work: the statistics strategy with VICReg —
    now one line through the StatsObjective protocol (fed_sim.stats_round
    with the registered dvicreg objective) instead of a hand-rolled round."""
    from repro import objectives as objectives_lib
    cfg, de, params0, apply, embed = _setup(seed=4)
    imgs, labels = synthetic.synthetic_labeled_images(400, 5, image_size=16,
                                                      noise=0.5, seed=4)
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=100, samples_per_client=2,
        alpha=0.0, seed=0)
    opt = opt_lib.adam(2e-3)
    obj = objectives_lib.get_objective("dvicreg")
    round_fn = jax.jit(lambda p, st, b, s: fed_sim.stats_round(
        apply, p, st, opt, b, s, objective=obj))
    state = opt.init(params0)
    p = params0
    t0 = time.perf_counter()
    for r in range(rounds):
        batch, sizes = ds.round_batch(jax.random.PRNGKey(700 + r), 16)
        p, state, m = round_fn(p, state, batch, sizes)
    us = (time.perf_counter() - t0) / rounds * 1e6
    acc0 = _probe(embed, params0, imgs, labels, n_train=300)
    acc = _probe(embed, p, imgs, labels, n_train=300)
    emit("dvicreg/federated", us,
         f"probe={acc:.3f}(init={acc0:.3f});loss={float(m.loss):.2f}")


def retrieval_serving(qn=64, n=4096, d=64, k=10,
                      corpus_sizes=(1024, 4096, 16384), serve_batches=20):
    """Retrieval serving: the fused MIPS top-k path vs the naive
    materialize-then-top_k program, plus QueryServer throughput/latency
    vs corpus size.

    Three row groups, two of them gated (benchmarks/compare.py):

      * compiled memory — XLA's own allocation plan (temp bytes) for both
        programs at the bench shape (Q=64, N=4096). The naive program
        materializes the (Q, N) f32 score matrix (temp >= Q*N*4 bytes);
        the fused path scans the corpus in chunks and keeps only the
        running (Q, k) state. GATED: the naive/fused temp ratio must not
        regress, and the fused temp must stay strictly under the score-
        matrix bytes (the subsystem's reason to exist) — both sides come
        from the same compiler in the same process, so the ratio is
        machine-portable.
      * calibrated fraction-of-roofline — the fused search's measured
        time vs the analytic bound (costmodel.mips_cost) evaluated with
        THIS machine's calibrated peaks (an in-process jitted matmul for
        flops/s, a big-array copy for bytes/s). GATED as a ratio of two
        same-process measurements; the TPU-spec analytic row rides along
        informationally.
      * QueryServer qps/p50/p99 vs corpus size — informational; the
        serving numbers a dashboard would track.
    """
    from benchmarks import costmodel
    from repro.kernels.mips_topk import mips_topk_chunked
    from repro.launch.mesh import HardwareSpec as HW
    from repro.retrieval import CorpusIndex, QueryServer, l2_normalize
    key = jax.random.PRNGKey(0)
    q = l2_normalize(jax.random.normal(key, (qn, d), jnp.float32))
    c = l2_normalize(jax.random.normal(jax.random.PRNGKey(1), (n, d),
                                       jnp.float32))
    naive = jax.jit(lambda q, c: jax.lax.top_k(q @ c.T, k))
    fused = jax.jit(lambda q, c: mips_topk_chunked(q, c, k=k, chunk=512))

    def temp_bytes(fn):
        """XLA's compiled temp allocation; degrades to 0 with a stderr
        notice on jax-version drift (the gate then fails loudly rather
        than the memory evidence vanishing silently)."""
        try:
            mem = fn.lower(q, c).compile().memory_analysis()
            return int(mem.temp_size_in_bytes)
        except Exception as e:  # pragma: no cover - jax-version drift
            print(f"retrieval_serving: compiled memory analysis "
                  f"unavailable ({type(e).__name__}: {e})", file=sys.stderr)
            return 0

    score_b = qn * n * 4
    naive_b, fused_b = temp_bytes(naive), temp_bytes(fused)
    emit("retrieval_serving/score_matrix_bytes", float(score_b),
         f"q{qn}_n{n}_d{d}_k{k}")
    emit("retrieval_serving/naive_temp_bytes", float(naive_b),
         f"materializes_QN={naive_b >= score_b}")
    emit("retrieval_serving/fused_temp_bytes", float(fused_b),
         f"naive_vs_fused={naive_b / max(fused_b, 1):.2f}x;"
         f"of_score_matrix={fused_b / score_b:.3f}")

    us_naive = _timeit(lambda: naive(q, c), n=5, best_of=4)
    us_fused = _timeit(lambda: fused(q, c), n=5, best_of=4)
    emit("retrieval_serving/naive_search", us_naive, f"q{qn}_n{n}_d{d}_k{k}")
    emit("retrieval_serving/fused_search", us_fused,
         f"fused_vs_naive_time={us_fused / us_naive:.2f}x")

    # calibrate this machine's achievable peaks in-process, then score the
    # fused search against the analytic bound at those peaks
    flops_s, bytes_s = _calibrate_peaks()
    cost = costmodel.mips_cost(qn, n, d, k)
    bound_us = max(cost.flops_dev / flops_s,
                   cost.hbm_bytes_dev / bytes_s) * 1e6
    emit("retrieval_serving/roofline_fraction_pct",
         100.0 * bound_us / us_fused,
         f"bound_us={bound_us:.1f};calib_gflops={flops_s / 1e9:.1f};"
         f"calib_GBps={bytes_s / 1e9:.1f}")
    ro = cost.roofline()
    emit("retrieval_serving/analytic_tpu_bound",
         ro["step_s_lower_bound"] * 1e6,
         f"dom={ro['dominant']};intensity={cost.notes['intensity_fused']:.1f};"
         f"spec={HW.PEAK_FLOPS_BF16 / 1e12:.0f}TF")

    qkey = jax.random.PRNGKey(3)
    qpool = l2_normalize(jax.random.normal(qkey, (serve_batches, 64, d),
                                           jnp.float32))
    for nn in corpus_sizes:
        idx = CorpusIndex(l2_normalize(jax.random.normal(
            jax.random.fold_in(qkey, nn), (nn, d), jnp.float32)))
        srv = QueryServer(idx, k=k, batch=64).warmup()
        for i in range(serve_batches):
            srv.query(qpool[i])
        s = srv.stats()
        emit(f"retrieval_serving/qserver_n{nn}", s["p50_us"],
             f"qps={s['qps']:.0f};qps_serial={s['qps_serial']:.0f};"
             f"p99_us={s['p99_us']:.0f};batches={s['batches']}")


def mixed_precision(rounds=10, cpr=16, arch="qwen3-1.7b", shape="train_4k"):
    """Mixed-precision encoders (EngineConfig.compute_dtype="bfloat16"):
    the encoder forward/backward narrows to bf16, every Eq.-3 statistic
    accumulation stays f32 (core/round_engine.cast_encoder_apply).

    Two row groups:

      * modeled step time (GATED) — costmodel.train_cost at the production
        arch/shape with compute_bytes={F32, BF16} and the matching MXU
        peak; the f32/bf16 bound ratio is the gated speedup. Modeled, not
        measured, because the gate must be machine-portable and XLA:CPU
        has no fast bf16 path (measured on this runner bf16 is SLOWER —
        the measured rows below record exactly that, informationally).
      * probe parity (GATED) — the same engine run at f32 vs bf16 compute
        on the bench encoder; the linear-probe accuracies ride in the
        us_per_call field and compare.py asserts |bf16 - f32| stays within
        tolerance. This is the numerics-contract acceptance: if bf16 ever
        leaks into the statistics accumulation, parity is what breaks.
    """
    from benchmarks import costmodel
    from repro.configs.base import get_dual_encoder_config, get_config as _gc
    from repro.launch.inputs import INPUT_SHAPES, arch_variant_for_shape
    from repro.launch.mesh import HardwareSpec as HW

    # --- modeled rows (the gated speedup)
    sh = INPUT_SHAPES[shape]
    mcfg = arch_variant_for_shape(_gc(arch), sh)
    de_proj = tuple(get_dual_encoder_config(arch).proj_dims)
    bounds = {}
    for label, cbytes, peak in (
            ("f32", costmodel.F32, HW.PEAK_FLOPS_F32),
            ("bf16", costmodel.BF16, HW.PEAK_FLOPS_BF16)):
        cost = costmodel.train_cost(mcfg, sh, multi_pod=False,
                                    de_proj=de_proj, compute_bytes=cbytes)
        ro = cost.roofline(peak)
        bounds[label] = ro["step_s_lower_bound"]
        emit(f"mixed_precision/{label}_step_model",
             ro["step_s_lower_bound"] * 1e6,
             f"{arch}/{shape};dom={ro['dominant']}")
    emit("mixed_precision/model_speedup", 0.0,
         f"bf16_vs_f32={bounds['f32'] / bounds['bf16']:.2f}x")

    # --- measured rows (wall-clock informational, probe parity gated)
    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=0.5, seed=1)
    cfg, de, params0, apply, embed = _setup()
    ds = pipeline.FederatedDataset.build(
        {"images": imgs}, labels, num_clients=128, samples_per_client=2,
        alpha=0.0, seed=0)
    sampler = ds.make_round_sampler(cpr)
    accs = {}
    for dtype in ("float32", "bfloat16"):
        opt = opt_lib.adam(2e-3)
        ecfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                         chunk_rounds=rounds,
                                         compute_dtype=dtype)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        p, _, m = eng.run(params0, opt.init(params0),
                          jax.random.PRNGKey(7), rounds)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        p2, _, _ = eng.run(params0, opt.init(params0),
                           jax.random.PRNGKey(7), rounds)
        jax.block_until_ready(p2)
        us = (time.perf_counter() - t0) / rounds * 1e6
        tag = "f32" if dtype == "float32" else "bf16"
        accs[tag] = _probe(embed, p, imgs, labels)
        emit(f"mixed_precision/{tag}_round_measured", us,
             f"loss={float(m.loss[-1]):.3f}")
        # emit() keeps one decimal, so ship acc x 1000 (milli-accuracy) to
        # preserve the resolution the parity gate compares at
        emit(f"mixed_precision/probe_{tag}", accs[tag] * 1000.0,
             "acc_x1000" if tag == "f32" else
             f"acc_x1000;d_acc={accs['bf16'] - accs['f32']:+.3f}")


def comm_round(cpr=16, bits_list=(32, 8, 4)):
    """The gated wall-clock cost of one federated comm round, dense vs
    quantized: encode/decode COMPUTE (measured, warmed, jitted) plus WIRE
    time (modeled at HardwareSpec.FED_UPLINK_BW — clients are phones on
    ~20 Mbit/s uplinks, the paper's setting; clients upload in parallel so
    the round waits on one payload).

    The payload is one realistic round's per-client uplink: the CCO stat
    template plus a full parameter-delta tree of the bench encoder,
    stacked K=cpr clients deep — the exact trees QuantizedChannel sees in
    phases 1 and 2. GATED in compare.py: the int8 round total must be <=
    the dense round total (HARD — compression must never cost wall-clock)
    and the int8/dense ratio must not regress. The fused whole-payload
    quantizer (comm.quantize.quant_dequant_payload) is what makes the
    compute side small enough for the wire saving to dominate.
    """
    from benchmarks import costmodel
    from repro import objectives as objectives_lib
    cfg, de, params0, apply, embed = _setup()
    key = jax.random.PRNGKey(0)
    stats_tmpl = objectives_lib.get_objective("dcco").stat_template(
        de.proj_dims[-1])
    stats_k = jax.tree.map(
        lambda s: jax.random.normal(key, (cpr,) + s.shape, jnp.float32),
        stats_tmpl)
    deltas_k = jax.tree.map(
        lambda p: 0.01 * jax.random.normal(key, (cpr,) + p.shape,
                                           jnp.float32), params0)
    n_elems = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(stats_tmpl))
    n_elems += sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params0))
    n_leaves = len(jax.tree.leaves(stats_tmpl)) + len(jax.tree.leaves(params0))
    sizes = jnp.full((cpr,), 2, jnp.int32)

    totals = {}
    for bits in bits_list:
        name = "dense" if bits == 32 else f"int{bits}"
        ch = comm.DenseChannel() if bits == 32 else comm.QuantizedChannel(bits)

        def both_phases(k1, stats_k, deltas_k, ch=ch):
            ctx = ch.begin_round(k1, sizes)
            return (ch.aggregate(ctx, stats_k, "stats"),
                    ch.aggregate(ctx, deltas_k, "update"))

        fn = jax.jit(both_phases)
        us_compute = _timeit(lambda: fn(key, stats_k, deltas_k), n=5)
        wire = costmodel.comm_round_cost(n_elems, bits)
        us_total = us_compute + wire["wire_s"] * 1e6
        totals[name] = us_total
        emit(f"comm_round/{name}_compute", us_compute,
             f"elems={n_elems};leaves={n_leaves};K={cpr}")
        emit(f"comm_round/{name}_round_model", us_total,
             f"wire_KB={wire['wire_bytes'] / 1e3:.0f};"
             f"wire_us={wire['wire_s'] * 1e6:.0f};"
             f"uplink_Mbps={8 * costmodel.HW.FED_UPLINK_BW / 1e6:.0f}")
    for name in totals:
        if name != "dense":
            emit(f"comm_round/{name}_vs_dense", 0.0,
                 f"ratio={totals[name] / totals['dense']:.3f}")


def kernel_roofline():
    """Calibrated fraction-of-roofline for the remaining federated Pallas
    kernels — `cco_stats`, `segment_sum`, `quantize` — extending the PR-7
    mips_topk gate to the whole kernel surface.

    Method (same as `retrieval_serving`): time the jitted REFERENCE
    implementation of each kernel's math (kernels/ref.py and the shared
    qdq formula — on this CPU runner the Pallas kernels run interpreted,
    which times the interpreter, not the algorithm), compute the analytic
    bound (costmodel.{cco_stats,segment_sum,quantize}_cost) at THIS
    machine's calibrated peaks, and emit the achieved fraction in percent.
    GATED in compare.py as a no-regress ratio; the analytic TPU rows live
    in roofline.build_kernel_table (the `roofline` bench).
    """
    from benchmarks import costmodel
    from repro.comm.quantize import _qdq_formula, qmax_for_bits
    from repro.kernels import ref
    flops_s, bytes_s = _calibrate_peaks()

    def fraction(name, fn, args, cost, n=5):
        us = _timeit(lambda: fn(*args), n=n, best_of=4)
        bound_us = max(cost.flops_dev / flops_s,
                       cost.hbm_bytes_dev / bytes_s) * 1e6
        emit(f"kernel_roofline/{name}_fraction_pct", 100.0 * bound_us / us,
             f"measured_us={us:.1f};bound_us={bound_us:.1f};"
             f"calib_gflops={flops_s / 1e9:.1f};"
             f"calib_GBps={bytes_s / 1e9:.1f}")

    key = jax.random.PRNGKey(0)
    n_rows, d = 4096, 512
    zf = jax.random.normal(key, (n_rows, d), jnp.float32)
    zg = jax.random.normal(jax.random.fold_in(key, 1), (n_rows, d),
                           jnp.float32)
    fraction("cco_stats", jax.jit(ref.cco_stats_ref), (zf, zg),
             costmodel.cco_stats_cost(n_rows, d))

    k_cl, d_st, e = 4096, 4352, 64
    rows = jax.random.normal(key, (k_cl, d_st), jnp.float32)
    seg = jax.random.randint(jax.random.fold_in(key, 2), (k_cl,), 0, e)
    w = jax.random.uniform(jax.random.fold_in(key, 3), (k_cl,), jnp.float32)
    fraction(
        "segment_sum",
        jax.jit(lambda r, s, w: ref.segment_sum_ref(r, s, e, weights=w)),
        (rows, seg, w), costmodel.segment_sum_cost(k_cl, d_st, e))

    kq, nq, bits = 256, 55296, 8
    qmax = qmax_for_bits(bits)
    flat = jax.random.normal(key, (kq, nq), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(key, 4), (kq, nq), jnp.float32)
    scales = jnp.abs(flat).max(axis=1) / qmax
    fraction("quantize",
             jax.jit(lambda f, u, s: _qdq_formula(f, u, s, qmax)),
             (flat, u, scales), costmodel.quantize_cost(kq, nq, bits))


def roofline_bench():
    rows = roofline_mod.build_table()
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
        emit(f"roofline/{r['arch']}/{r['shape']}",
             r["step_lower_bound_s"] * 1e6,
             f"dom={r['dominant']};useful={r['useful_ratio']:.2f}")
    emit("roofline/summary", 0.0,
         ";".join(f"{k}={v}" for k, v in sorted(doms.items())))
    for r in roofline_mod.build_mips_table() + roofline_mod.build_kernel_table():
        emit(f"roofline/{r['arch']}/{r['shape']}",
             r["step_lower_bound_s"] * 1e6,
             f"dom={r['dominant']};"
             f"fused_vs_naive_bound={r['fused_vs_naive_bound']:.2f}x;"
             f"intensity={r['intensity_fused']:.1f}")


def retrieval_scale(qn=64, n=8192, d=64, k=10, shards=4,
                    num_centroids=256, nprobe=4,
                    nprobe_curve=(1, 2, 4, 8, 16),
                    refresh_n=4096, refresh_block=64):
    """Retrieval at scale: sharded exact search, the IVF approximate
    tier's recall-vs-qps curve, and drift-gated streaming refresh.

    Gated rows (benchmarks/compare.py; every gate is a same-process ratio
    or a deterministic count — machine-portable):

      * sharded — one shard's local fused search (N/S rows, offset
        contract) + the S·k candidate merge are timed separately; the
        modeled S-device parallel time (shard_us + merge_us — the
        all-gather moves S*Q*k entries, noise at these shapes) must BEAT
        the measured single-device exact search (HARD: sharding must
        never slow a fixed-size search down), and the vmap-sharded result
        must match single-device search bit-for-bit (HARD);
      * ivf — recall@10 vs the exact ground truth at the default nprobe
        (HARD floor: >= 0.95, x1000 row) while the pruned search beats
        the exact tier's latency (HARD ratio > 1). The corpus is
        clustered (items around num_centroids centers — embedding
        corpora cluster; uniform-random would be IVF's pathological
        no-structure case) and the per-nprobe curve rows record the
        recall-vs-qps tradeoff;
      * refresh — a two-group linear-encoder scenario where perturbing
        one weight block drifts exactly a quarter of the corpus: the
        drift-gated refresh must re-encode < 50% of the items a full
        rebuild would (HARD, includes probe overhead) while its
        post-refresh top-k matches the full rebuild's (HARD parity,
        x1000).
    """
    from benchmarks import costmodel
    from repro.kernels.mips_topk import mips_topk
    from repro.retrieval import (CorpusIndex, IVFIndex, l2_normalize,
                                 refresh_embeddings)
    from repro.retrieval.sharded import (merge_topk, sharded_mips_topk,
                                         stack_shards)

    # clustered corpus: items around true_c natural clusters (embedding
    # corpora cluster — k-means then sub-divides each, true_c = C/2),
    # queries near cluster centers with smaller noise than the items
    key = jax.random.PRNGKey(0)
    true_c = max(1, num_centroids // 2)
    centers = l2_normalize(jax.random.normal(key, (true_c, d), jnp.float32))
    per = -(-n // true_c)
    noise = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (n, d),
                                    jnp.float32)
    c = l2_normalize(jnp.repeat(centers, per, axis=0)[:n] + noise[:n])
    qg = jax.random.randint(jax.random.PRNGKey(2), (qn,), 0, true_c)
    q = l2_normalize(centers[qg] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), (qn, d), jnp.float32))

    # ---- sharded exact tier ------------------------------------------------
    exact = jax.jit(lambda q, c: mips_topk(q, c, k, backend="chunked"))
    ev, ei = jax.block_until_ready(exact(q, c))
    us_exact = _timeit(lambda: exact(q, c), n=5, best_of=4)
    emit("retrieval_scale/exact_search", us_exact, f"q{qn}_n{n}_d{d}_k{k}")

    shard_stack = stack_shards(c, shards)
    shard_size = shard_stack.shape[1]
    local = jax.jit(lambda q, s: mips_topk(
        q, s, k, backend="chunked", index_offset=jnp.zeros((), jnp.int32),
        n_total=n))
    jax.block_until_ready(local(q, shard_stack[0]))
    us_shard = _timeit(lambda: local(q, shard_stack[0]), n=5, best_of=4)
    cand_v = jnp.tile(ev[None], (shards, 1, 1))
    cand_i = jnp.tile(ei[None], (shards, 1, 1))
    merge = jax.jit(lambda v, i: merge_topk(v, i, k))
    jax.block_until_ready(merge(cand_v, cand_i))
    us_merge = _timeit(lambda: merge(cand_v, cand_i), n=5, best_of=4)
    emit("retrieval_scale/shard_local_search", us_shard,
         f"rows={shard_size};shards={shards}")
    emit("retrieval_scale/shard_merge", us_merge,
         f"candidates={shards * k}_per_query")
    emit("retrieval_scale/sharded_speedup_modeled",
         us_exact / (us_shard + us_merge),
         f"exact_us={us_exact:.0f};shard_us={us_shard:.0f};"
         f"merge_us={us_merge:.0f};allgather_entries={shards * qn * k}")

    sv, si = jax.block_until_ready(
        jax.jit(lambda q, s: sharded_mips_topk(
            q, s, k, n_total=n, backend="chunked"))(q, shard_stack))
    bit = bool(jnp.array_equal(sv, ev)) and bool(jnp.array_equal(si, ei))
    emit("retrieval_scale/sharded_exact_match", float(bit),
         f"bitwise_scores_and_indices;shards={shards}")

    # ---- IVF approximate tier ----------------------------------------------
    ivf = IVFIndex.from_index(CorpusIndex(c), num_centroids=num_centroids,
                              nprobe=nprobe, seed=7)
    truth = set_rows = [set(np.asarray(ei)[i].tolist()) for i in range(qn)]

    def recall_at_k(idx_arr):
        got = np.asarray(idx_arr)
        return float(np.mean([len(set(got[i]) & truth[i]) / k
                              for i in range(qn)]))

    if nprobe > num_centroids:
        raise ValueError(
            f"nprobe={nprobe} exceeds num_centroids={num_centroids}; the "
            f"default-nprobe gate rows would have nothing to measure")
    us_default = rec_default = None
    for p in sorted(set(tuple(nprobe_curve) + (nprobe,))):
        if p > num_centroids:
            continue
        run = jax.jit(functools.partial(ivf.search, k=k, nprobe=p))
        _, pi = jax.block_until_ready(run(q))
        us_p = _timeit(lambda: run(q), n=5, best_of=4)
        rec = recall_at_k(pi)
        emit(f"retrieval_scale/ivf_search_nprobe{p}", us_p,
             f"recall_at{k}={rec:.3f};qps_vs_exact={us_exact / us_p:.2f}x;"
             f"scan_rows={p * ivf.list_len}")
        if p == nprobe:
            us_default, rec_default = us_p, rec
    emit("retrieval_scale/ivf_recall_at10_x1000", 1000.0 * rec_default,
         f"nprobe={nprobe};C={num_centroids};fill={ivf.fill:.2f}")
    emit("retrieval_scale/ivf_qps_ratio", us_exact / us_default,
         f"exact_us={us_exact:.0f};ivf_us={us_default:.0f};nprobe={nprobe}")
    cost = costmodel.ivf_cost(qn, n, d, k, num_centroids=num_centroids,
                              nprobe=nprobe, list_len=ivf.list_len)
    emit("retrieval_scale/ivf_cost_flops_ratio",
         cost.notes["flops_ratio_exact_over_ivf"],
         f"scan_rows={cost.notes['scan_rows']:.0f};"
         f"intensity={cost.notes['intensity']:.1f}")

    # ---- drift-gated streaming refresh -------------------------------------
    # two-group linear encoder: items 0..m-1 read only the first feature
    # block, the rest only the second — perturbing W's first block drifts
    # exactly the first quarter of the corpus
    d_in, m = 32, refresh_n // 4
    w = jax.random.normal(jax.random.PRNGKey(11), (d_in, d),
                          jnp.float32) * 0.3
    feats = jax.random.normal(jax.random.PRNGKey(12), (refresh_n, d_in),
                              jnp.float32)
    feats = feats.at[:m, d_in // 2:].set(0.0).at[m:, :d_in // 2].set(0.0)
    enc = lambda p, x: x @ p  # noqa: E731
    emb0 = jax.block_until_ready(l2_normalize(enc(w, feats)))
    w2 = w.at[:d_in // 2].add(0.15 * jax.random.normal(
        jax.random.PRNGKey(13), (d_in // 2, d), jnp.float32))
    new_emb, rstats = jax.jit(functools.partial(
        refresh_embeddings, enc, threshold=1e-3, block=refresh_block,
        probes_per_block=4))(w2, feats, emb0)
    frac_items = float(rstats["items_encoded"]) / refresh_n
    full = l2_normalize(enc(w2, feats))
    qr = l2_normalize(jax.random.normal(jax.random.PRNGKey(14), (qn, d),
                                        jnp.float32))
    _, ri = mips_topk(qr, new_emb, k, backend="chunked")
    _, fi = mips_topk(qr, full, k, backend="chunked")
    parity = float(np.mean([
        len(set(np.asarray(ri)[i]) & set(np.asarray(fi)[i])) / k
        for i in range(qn)]))
    emit("retrieval_scale/refresh_items_ratio_x1000", 1000.0 * frac_items,
         f"items_encoded={float(rstats['items_encoded']):.0f}_of_{refresh_n};"
         f"blocks={float(rstats['blocks_refreshed']):.0f};"
         f"probe_overhead_included=True")
    emit("retrieval_scale/refresh_recall_parity_x1000", 1000.0 * parity,
         f"top{k}_overlap_vs_full_rebuild;drifted_quarter=True")


def heterogeneity_sweep(rounds=25, cpr=16, clusters=2,
                        train_strategies=("label", "dirichlet"),
                        severities=(0.0, 0.9), train_severities=None):
    """Client-heterogeneity scenario suite (repro.data.partition) x
    cluster-aware aggregation (repro.cluster).

    Partition rows cut each registered strategy at each severity and
    report the cut time plus the label-dominance skew metric — the
    evidence that the normalized severity axis is real (dominance rises
    with severity for the skewing strategies, stays flat for controls).

    Probe rows train the same DCCO engine twice per (strategy, severity)
    cell — global single-model aggregation vs ``EngineConfig.num_clusters``
    cluster-aware slots — then evaluate BOTH on the same cluster-matched
    subsets: every client is assigned to a cluster with the trained
    centroids, each cluster's samples are probed under that cluster's
    params (clustered row) and under the global run's params (global
    row), and the rows report the sample-weighted mean accuracy
    (acc x 1000 in the us field, the mixed_precision convention). The
    compare.py gate holds clustered >= global at severity >= 0.8 — the
    regime where one averaged model straddles a mixture — with a
    no-regress floor on the clustered accuracy. Both probes are
    deterministic given the seeds, so the gate carries no machine noise.
    """
    from repro import cluster as cluster_lib
    from repro import objectives as objectives_lib
    from repro.data import partition as partition_lib

    imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=16,
                                                      noise=1.0, seed=1)
    ncls = int(labels.max()) + 1
    cfg, de, params0, apply, embed = _setup()
    obj = objectives_lib.get_objective("dcco")

    for strategy in partition_lib.PARTITIONS:
        for sev in severities:
            spec = partition_lib.PartitionSpec(strategy, sev)
            t0 = time.perf_counter()
            idx, sizes = partition_lib.build_partition(
                spec, labels, num_clients=300, samples_per_client=2, seed=0)
            us = (time.perf_counter() - t0) * 1e6
            dom = partition_lib.label_dominance(labels, idx, sizes)
            emit(f"heterogeneity_sweep/partition/{strategy}/sev{sev:.1f}",
                 us, f"dominance={dom:.3f}")

    def subset_probe(z, sel):
        """Ridge probe on one cluster's samples: even rows train, odd
        rows test (class-interleaved by the partition's construction)."""
        zs, ys = z[sel], jnp.asarray(labels[sel])
        return float(eval_lib.ridge_linear_probe(
            zs[0::2], ys[0::2], zs[1::2], ys[1::2], ncls))

    for strategy in train_strategies:
        for sev in (severities if train_severities is None
                    else train_severities):
            ds = pipeline.FederatedDataset.build(
                {"images": imgs}, labels, num_clients=300,
                samples_per_client=2,
                partition=partition_lib.PartitionSpec(strategy, sev),
                seed=0)
            sampler = ds.make_round_sampler(cpr)
            ecfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0,
                                             chunk_rounds=rounds)
            opt_g = opt_lib.adam(2e-3)
            eng_g = round_engine.RoundEngine(apply, opt_g, sampler, ecfg)
            t0 = time.perf_counter()
            pg, _, _ = eng_g.run(params0, opt_g.init(params0),
                                 jax.random.PRNGKey(7), rounds)
            us_g = (time.perf_counter() - t0) / rounds * 1e6
            opt_c = opt_lib.adam(2e-3)
            eng_c = round_engine.RoundEngine(
                apply, opt_c, sampler, ecfg._replace(num_clusters=clusters))
            t0 = time.perf_counter()
            pc, _, _ = eng_c.run(params0, opt_c.init(params0),
                                 jax.random.PRNGKey(7), rounds)
            us_c = (time.perf_counter() - t0) / rounds * 1e6
            cs = eng_c.cluster_state

            # assign EVERY client with the trained centroids (stats under
            # the clustered readout, identical views — assignment only)
            def client_stats(x):
                zf, zg = apply(pc, {"v1": x, "v2": x})
                return obj.stats_masked(zf, zg, jnp.ones(x.shape[0]))

            st_k = jax.vmap(client_stats)(
                jnp.asarray(imgs[ds.client_index]))
            ids = np.asarray(cluster_lib.assign_clusters(
                cluster_lib.flatten_stats(st_k), cs.centroids))
            z_g = embed(pg, jnp.asarray(imgs))
            acc_g = acc_c = wsum = 0.0
            for c in range(clusters):
                sel = np.unique(ds.client_index[ids == c].reshape(-1))
                if len(sel) < 2 * ncls:
                    continue                     # degenerate-probe cluster
                p_c = jax.tree.map(lambda x: x[c], cs.params_c)
                z_c = embed(p_c, jnp.asarray(imgs[sel]))
                w = float(len(sel))
                acc_c += w * subset_probe(z_c, np.arange(len(sel)))
                acc_g += w * subset_probe(z_g, sel)
                wsum += w
            acc_g, acc_c = acc_g / wsum, acc_c / wsum
            tag = f"heterogeneity_sweep/probe/{strategy}/sev{sev:.1f}"
            emit(f"{tag}/global_x1000", acc_g * 1000.0,
                 f"acc_x1000;round_us={us_g:.0f}")
            emit(f"{tag}/clustered_x1000", acc_c * 1000.0,
                 f"acc_x1000;d_acc={acc_c - acc_g:+.3f};"
                 f"clusters={clusters};round_us={us_c:.0f}")


BENCHES = {
    "table1": table1_cifar,
    "table2": table2_derm,
    "figure3": figure3_collapse,
    "dcco_round": dcco_round_bench,
    "round_engine": round_engine_bench,
    "async_stragglers": async_stragglers,
    "comm_sweep": comm_sweep,
    "server_opt_sweep": server_opt_sweep,
    "fused_step": fused_step_bench,
    "stats_kernel": stats_kernel_bench,
    "stale_stats": stale_stats_study,
    "dvicreg": dvicreg_bench,
    "objective_sweep": objective_sweep,
    "population_scale": population_scale,
    "retrieval_serving": retrieval_serving,
    "retrieval_scale": retrieval_scale,
    "mixed_precision": mixed_precision,
    "heterogeneity_sweep": heterogeneity_sweep,
    "comm_round": comm_round,
    "kernel_roofline": kernel_roofline,
    "roofline": roofline_bench,
}

# reduced sizes for the CI bench-smoke gate (BENCH_SMOKE=1): enough rounds
# for the engine-vs-loop speedup ratio to stabilize, small enough for a
# shared CPU runner
SMOKE_KW = {
    "round_engine": {"rounds": 40},
    # ticks-per-update ratios are exact functions of the latency stream,
    # so the smoke run may shrink wall time without moving the gate
    "async_stragglers": {"ticks": 24},
    "comm_sweep": {"rounds": 8},
    "server_opt_sweep": {"rounds": 8},
    "objective_sweep": {"rounds": 8},
    "stats_kernel": {"sizes": ((512, 256),)},
    "table1": {"rounds": 8},
    "table2": {"rounds": 8},
    # the 4096-client streaming smoke must stay: it is the acceptance
    # check that mega-cohorts actually fit on a shared CPU runner
    "population_scale": {"rounds": 2, "cohorts": (64, 256, 4096),
                         "chunk": 64, "materialize_max": 256},
    # the gated memory + roofline-fraction rows keep the full bench shape
    # (the Q=64 x N=4096 acceptance size); only the latency sweep shrinks
    "retrieval_serving": {"corpus_sizes": (1024, 4096), "serve_batches": 8},
    # the gated ratios (sharded modeled speedup, ivf recall/qps, refresh
    # fraction/parity) hold at the smaller smoke corpus; only wall time
    # shrinks
    "retrieval_scale": {"n": 4096, "num_centroids": 128,
                        "nprobe_curve": (1, 2, 4, 8), "refresh_n": 2048},
    # modeled rows are shape-exact at any round count; only the measured
    # parity runs shrink (parity is a tolerance check, not a ratio)
    "mixed_precision": {"rounds": 6},
    # the gated clustered-vs-global pair (label @ severity 0.9) must stay;
    # dropping the dirichlet and low-severity training cells keeps the
    # deterministic accuracy contract while fitting the CI runner
    "heterogeneity_sweep": {"rounds": 10, "train_strategies": ("label",),
                            "train_severities": (0.9,)},
    # comm_round / kernel_roofline time single jitted calls at the
    # acceptance shapes — already smoke-sized
}


def main(argv=None) -> None:
    names = list(sys.argv[1:] if argv is None else argv) or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benchmarks {unknown}; "
                         f"available: {list(BENCHES)}")
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n](**(SMOKE_KW.get(n, {}) if smoke else {}))
    print(f"# {len(ROWS)} benchmark rows")
    out_path = os.environ.get("BENCH_JSON", "BENCH.json")
    with open(out_path, "w") as f:
        json.dump({"benchmarks": names, "rows": ROWS}, f, indent=1)
    print(f"# wrote {out_path}")


if __name__ == "__main__":
    main()
