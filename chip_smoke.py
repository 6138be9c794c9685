#!/usr/bin/env python3
"""Chip smoke test: run the DCCO training main path on a TPU and check it.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded-cohort path on four chips

One chip (everything in this one process, which owns the chip):

  * train: ``repro.launch.train.main`` in engine mode with the paper's full
    ResNet-14 dual encoder (WS+GN, 64/128/256 channels, [1024,1024,1024]
    head, lambda=20), 20 rounds in scan segments of 10, on a synthetic
    CIFAR-100-shaped set (50,000 32x32x3 images, 100 classes) cut into
    two-sample single-class clients, 256 clients per round, f32 parameters
    and activations at the chip's default matmul precision;
  * (a) Appendix A: one engine DCCO round (one local step, client lr 1)
    against ``fed_sim.centralized_step`` on the same cohort, all at
    ``highest`` matmul precision, once with the phase-1 statistics in jnp
    and once through the Pallas kernel (``stats_kernel="pallas"``); the
    round at the chip's default precision is printed as an observation;
  * (b) each Pallas kernel of the training path, reached through its
    flag (``stats_kernel`` / ``quant_kernel`` / ``fold_impl`` /
    ``mips_topk(backend="auto")``), compiled at full width and checked
    against its jnp reference; each compiled program must contain a
    ``tpu_custom_call``, so an interpreted kernel cannot pass.

Four chips (``--chips 4``, and nothing else): one engine round with the
cohort sharded over a 4-device mesh (64 clients per chip) against the
one-device engine on the same keys, both at ``highest`` precision.

Losses, times and memory printed on the way are observations of one smoke
run, not benchmark numbers. Any failed check raises, so the script exits
non-zero; the last line of standard output is a JSON object, printed only
when every phase passed. With no TPU, it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# the paper-scale run of the trainer's main path
DATASET_SIZE = 50000
CLIENTS_PER_ROUND = 256
ROUNDS, CHUNK_ROUNDS = 20, 10
LAM = 20.0
# the library checks run on a 4,096-image draw of the same synthetic set:
# the cohort (256 two-sample clients) is the trainer's, the data scale only
# sets how many clients the cohort is drawn from
CHECK_DATASET_SIZE = 4096


def train_argv(dataset_size: int) -> list:
    return ["--mode", "engine", "--full", "--arch", "resnet14-cifar",
            "--dataset-size", str(dataset_size), "--num-classes", "100",
            "--samples-per-client", "2",
            "--clients-per-round", str(CLIENTS_PER_ROUND),
            "--rounds", str(ROUNDS), "--chunk-rounds", str(CHUNK_ROUNDS),
            "--eval-every", str(CHUNK_ROUNDS), "--lam", str(LAM),
            "--seed", "0"]

# (a): with the engine round and the reference both at "highest" matmul
# precision (f32 products on the TPU), Appendix A says they differ only by
# float re-association of the per-client sums, which is O(1e-6) relative
# per sum (measured on the CPU, where every product is f32: see PERF.md).
# A protocol fault (weights, stop-gradient, cohort, normalizer) moves the
# update by far more: a K/(K-1) normalizer slip alone is 4e-3 at K=256.
# Both rounds, with and without the Pallas statistics kernel, are held to
# this bound; the round at the chip's default precision (one bf16 pass
# per f32 product, as the trainer runs) is printed, not checked.
APPENDIX_A_BOUND = 1e-3
# (b): a kernel may differ from its reference by two bf16 roundings (unit
# roundoff 2^-8 each) of the magnitudes involved — one MXU pass at default
# precision rounds both f32 operands of a product to bf16; a wrong tile,
# block map or mask is off by O(1)
KERNEL_REL_BOUND = 2.0 ** -7
# --chips 4: the sharded and one-device programs (both at "highest"
# precision, so no bf16 rounding can flip between them) run the same
# per-client math and differ only in summation order: their parameters may
# differ by float re-association relative to the size of the update (see
# PERF.md for the CPU reading on 4 forced devices)
SHARDED_REL_BOUND = 1e-3


class SmokeFailure(AssertionError):
    """A phase's result is outside its bound."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def observe(msg: str) -> None:
    print(f"[smoke observation] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling in this process
    (its ``/jax/core/compile/*`` monitoring events)."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def peak_hbm(device) -> str:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return (f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


def compiled_with_kernel(jax, fn, *args):
    """jit + lower + compile ``fn`` for ``args``; fail unless a Pallas
    kernel was compiled into it (not interpreted)."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          f"compiled program (kernel interpreted or not reached)")
    return compiled


# ---------------------------------------------------------------- phases --

def phase_train(jax, clock, device):
    from repro.launch import train
    before = clock.seconds
    t0 = time.perf_counter()
    out = train.main(train_argv(DATASET_SIZE) + ["--ckpt-dir", OUT_DIR])
    wall = time.perf_counter() - t0
    hist, seg = out["history"], out["segment_seconds"]
    check(len(hist) == ROUNDS, f"train: {len(hist)} rounds ran, expected "
                               f"{ROUNDS}")
    check(all(math.isfinite(x) for x in hist),
          f"train: non-finite loss in {hist}")
    check(len(seg) == ROUNDS // CHUNK_ROUNDS,
          f"train: {len(seg)} segments, expected {ROUNDS // CHUNK_ROUNDS}")
    observe(f"train: loss round 1 {hist[0]:.4f}, round {CHUNK_ROUNDS} "
            f"{hist[CHUNK_ROUNDS - 1]:.4f}, round {ROUNDS} {hist[-1]:.4f}; "
            f"probe accuracy {out['probe']:.4f}")
    observe(f"train: {seg[-1] / CHUNK_ROUNDS:.4f} s/round over the last "
            f"{CHUNK_ROUNDS}-round segment; first segment {seg[0]:.2f} s "
            f"(includes its "
            f"compilation); compile {clock.seconds - before:.2f} s "
            f"(trace + lower + compile, whole phase); wall {wall:.2f} s")
    observe(f"train: peak HBM {peak_hbm(device)}")
    observe(f"train: memory_stats {device.memory_stats()}")


def _tree_rel(jax, jnp, a, b, origin):
    """||a - b|| / ||b - origin|| over a whole parameter tree (L2)."""
    def norm(t):
        return math.sqrt(sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                             for x in jax.tree.leaves(t)))
    diff = jax.tree.map(lambda x, y: x - y, a, b)
    upd = jax.tree.map(lambda x, y: x - y, b, origin)
    return norm(diff) / norm(upd)


def _full_width():
    from repro.configs.base import (DualEncoderConfig, get_config,
                                    get_dual_encoder_config)
    cfg = get_config("resnet14-cifar")
    de_cfg = DualEncoderConfig(
        proj_dims=get_dual_encoder_config("resnet14-cifar").proj_dims,
        lambda_cco=LAM)
    return cfg, de_cfg


def _check_dataset(cfg):
    from repro.launch import train
    args = train.build_parser().parse_args(train_argv(CHECK_DATASET_SIZE))
    ds, _ = train.build_dataset(cfg, args)
    return ds


def phase_appendix_a(jax, jnp):
    from repro.core import fed_sim, round_engine
    from repro.launch import train
    from repro.models import dual_encoder
    from repro.optim import optimizers as opt_lib

    cfg, de_cfg = _full_width()
    ds = _check_dataset(cfg)
    apply = train.make_apply(cfg, de_cfg)
    params = dual_encoder.init_dual_encoder(jax.random.PRNGKey(0), cfg,
                                            de_cfg)
    opt = opt_lib.sgd(0.05)
    sampler = ds.make_round_sampler(CLIENTS_PER_ROUND)
    rng = jax.random.PRNGKey(1)

    def engine_round(stats_kernel, precision):
        ecfg = round_engine.EngineConfig(
            algorithm="dcco", lam=LAM, client_lr=1.0, local_steps=1,
            chunk_rounds=1, stats_kernel=stats_kernel)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        carry = round_engine.EngineCarry(params, opt.init(params), rng)
        start = jnp.asarray(0, jnp.int32)
        with jax.default_matmul_precision(precision):
            compiled = eng._segment_fn(1).lower(
                carry, start, eng.sampler).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        check(has_kernel == (stats_kernel == "pallas"),
              f"engine round (stats_kernel={stats_kernel!r}): "
              f"tpu_custom_call {'missing from' if not has_kernel else 'in'}"
              f" the compiled segment")
        # the segment donates its carry: hand it copies
        carry = jax.tree.map(jnp.copy, carry)
        out, m = compiled(carry, start, eng.sampler)
        check(bool(jnp.isfinite(m.loss).all()),
              f"engine round ({stats_kernel}, {precision}): non-finite loss")
        return out.params

    p_hi = engine_round("off", "highest")
    p_kern = engine_round("pallas", "highest")
    p_def = engine_round("off", "default")

    # the cohort the engine drew in round 0 (RoundEngine._run_segment)
    k_sel, k_aug = jax.random.split(jax.random.fold_in(rng, 0))
    batch, sizes = jax.jit(lambda s, a, b: s(a, b))(sampler, k_sel, k_aug)
    n = jax.tree.leaves(batch)[0].shape[1]
    union = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
    mask = fed_sim._client_masks(sizes, n).reshape(-1)
    with jax.default_matmul_precision("highest"):
        p_ref = jax.jit(lambda p, s, u, mk: fed_sim.centralized_step(
            apply, p, s, opt, u, mask=mk, lam=LAM)[0])(
                params, opt.init(params), union, mask)

    for name, p in (("engine round", p_hi),
                    ("engine round, stats_kernel='pallas'", p_kern)):
        err = _tree_rel(jax, jnp, p, p_ref, params)
        print(f"appendix-A: {name} vs centralized step, both at highest "
              f"precision: relative update difference {err:.3e} (bound "
              f"{APPENDIX_A_BOUND:g})", flush=True)
        check(err <= APPENDIX_A_BOUND,
              f"appendix-A: {name} {err:.3e} > {APPENDIX_A_BOUND:g}")
    observe(f"appendix-A: stats_kernel='pallas' vs 'off' round, both at "
            f"highest precision: {_tree_rel(jax, jnp, p_kern, p_hi, params):.3e}")
    observe(f"appendix-A: engine round at the default matmul precision "
            f"(the trainer's) vs the highest-precision reference: "
            f"{_tree_rel(jax, jnp, p_def, p_ref, params):.3e} (not checked)")


def phase_kernels(jax, jnp):
    from repro import comm
    from repro.hierarchy import aggregation
    from repro.kernels import ref
    from repro.kernels.cco_stats import cco_stats_pallas
    from repro.kernels.mips_topk import mips_topk
    from repro.kernels.ops import on_tpu

    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    d = 1024                                  # the paper's head width

    def rel_err(out, want):
        return float(jnp.max(jnp.abs(out - want)) /
                     jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))

    # cco_stats, both moment sets, N = one round's 512 samples per view
    zf = jax.random.normal(keys[0], (2 * CLIENTS_PER_ROUND, d), jnp.float32)
    zg = zf + 0.5 * jax.random.normal(keys[1], zf.shape, jnp.float32)
    for moments in ("cross", "full"):
        fn = functools.partial(cco_stats_pallas, moments=moments,
                               interpret=not on_tpu())
        out = compiled_with_kernel(jax, fn, zf, zg)(zf, zg)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(
                ref.cco_stats_ref, second_moments=moments == "full"))(zf, zg)
        err = max(rel_err(out[k], want[k]) for k in want)
        print(f"kernel cco_stats[{moments}] 512x{d}: max relative error "
              f"{err:.3e} (bound {KERNEL_REL_BOUND:.3e})", flush=True)
        check(err <= KERNEL_REL_BOUND, f"cco_stats[{moments}]: {err:.3e}")

    # per-client CCO statistics of a 64-client cohort at d=1024: the
    # payload the quantized channel and the hierarchy fold carry
    k_cl = 64
    zk = jax.random.normal(keys[2], (k_cl, 2, d), jnp.float32)
    stats_k = jax.jit(jax.vmap(ref.cco_stats_ref))(zk, zk * 0.5 + 0.1)
    sizes = jnp.full((k_cl,), 2, jnp.int32)

    # quant_dequant_pallas through QuantizedChannel(8, kernel="pallas")
    def wire(channel):
        def fn(key, sizes, tree):
            return channel.encode_decode(channel.begin_round(key, sizes),
                                         tree, "stats")
        fn.__name__ = repr(channel)
        return fn
    qkey = keys[3]
    q_fn = wire(comm.QuantizedChannel(8, kernel="pallas"))
    got = compiled_with_kernel(jax, q_fn, qkey, sizes, stats_k)(
        qkey, sizes, stats_k)
    want = jax.jit(wire(comm.QuantizedChannel(8, kernel="off")))(
        qkey, sizes, stats_k)
    worst, same, total = 0.0, 0, 0
    for k in want:
        x = stats_k[k].reshape(k_cl, -1)
        step = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        diff = jnp.abs(got[k] - want[k]).reshape(k_cl, -1)
        worst = max(worst, float(jnp.max(diff / jnp.maximum(step, 1e-30))))
        same += int(jnp.sum(diff == 0))
        total += diff.size
    print(f"kernel quant_dequant (QuantizedChannel(8, 'pallas')), {k_cl} "
          f"clients x {total // k_cl} values: max difference from the jnp "
          f"wire {worst:.3f} quantization steps (bound 1), {same / total:.6f} "
          f"of values identical", flush=True)
    check(worst <= 1.0 + 1e-5, f"quant_dequant: {worst:.3f} steps")

    # segment_sum_pallas through the hierarchy fold (fold_impl="pallas"):
    # the whole stats payload flattened into one row per client
    edges = 8
    ids = aggregation.contiguous_edge_ids(k_cl, edges)
    w = sizes.astype(jnp.float32) / jnp.sum(sizes)

    def fold(tree, w, ids):
        return aggregation.fold_to_edges(tree, w, ids, edges, impl="pallas")
    got = compiled_with_kernel(jax, fold, stats_k, w, ids)(stats_k, w, ids)
    err = 0.0
    for k in stats_k:
        rows = stats_k[k].reshape(k_cl, -1)
        want_k = ref.segment_sum_ref(rows, ids, edges, w)
        scale = ref.segment_sum_ref(jnp.abs(rows), ids, edges, w)
        err = max(err, float(jnp.max(jnp.abs(
            got[k].reshape(edges, -1) - want_k) / jnp.maximum(scale, 1e-30))))
    row_width = sum(int(v[0].size) for v in stats_k.values())
    print(f"kernel segment_sum (fold_impl='pallas'), {k_cl} clients -> "
          f"{edges} edges, row width {row_width}: max error relative to the "
          f"summed magnitudes {err:.3e} (bound {KERNEL_REL_BOUND:.3e})",
          flush=True)
    check(err <= KERNEL_REL_BOUND, f"segment_sum: {err:.3e}")

    # mips_topk(backend="auto") on a 65,536 x 1024 index, unit rows
    def unit(x):
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)
    q = unit(jax.random.normal(keys[4], (256, d), jnp.float32))
    corpus = unit(jax.random.normal(keys[5], (65536, d), jnp.float32))
    top_k = 10

    def search(q, corpus):
        return mips_topk(q, corpus, top_k)
    vals, idx = compiled_with_kernel(jax, search, q, corpus)(q, corpus)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(lambda a, b: a @ b.T)(q, corpus)
    kth = jax.lax.top_k(exact, top_k)[0][:, -1:]
    picked = jnp.take_along_axis(exact, idx, axis=1)
    score_err = float(jnp.max(jnp.abs(vals - picked)))
    rank_gap = float(jnp.max(kth - picked))
    # unit vectors: a score's error is at most KERNEL_REL_BOUND, and an
    # item can displace the true k-th only by twice that
    print(f"kernel mips_topk (backend='auto'), 256 queries x 65536 items, "
          f"k={top_k}: returned scores within {score_err:.3e} of exact "
          f"(bound {KERNEL_REL_BOUND:.3e}); worst returned item "
          f"{rank_gap:.3e} below the exact k-th score (bound "
          f"{2 * KERNEL_REL_BOUND:.3e})", flush=True)
    check(score_err <= KERNEL_REL_BOUND and rank_gap <= 2 * KERNEL_REL_BOUND,
          f"mips_topk: score error {score_err:.3e}, rank gap {rank_gap:.3e}")


def phase_sharded(jax, jnp, clock):
    from repro.core import round_engine
    from repro.launch import train
    from repro.models import dual_encoder
    from repro.optim import optimizers as opt_lib

    cfg, de_cfg = _full_width()
    ds = _check_dataset(cfg)
    apply = train.make_apply(cfg, de_cfg)
    params = dual_encoder.init_dual_encoder(jax.random.PRNGKey(0), cfg,
                                            de_cfg)
    # plain SGD on the server: its update is the averaged client delta, so
    # the parameter difference measures the aggregation itself (Adam's
    # first steps are ~lr * sign(g) and would flip on near-zero gradients)
    opt = opt_lib.sgd(0.05)
    sampler = ds.make_round_sampler(CLIENTS_PER_ROUND)
    rng = jax.random.PRNGKey(3)
    rounds = 1
    mesh = jax.make_mesh((4,), ("data",))

    def run(cohort_axis, mesh):
        ecfg = round_engine.EngineConfig(
            algorithm="dcco", lam=LAM, chunk_rounds=rounds,
            cohort_axis=cohort_axis)
        eng = round_engine.RoundEngine(apply, opt, sampler, ecfg, mesh=mesh)
        before = clock.seconds
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            p, _, m = eng.run(params, opt.init(params), rng, rounds)
        jax.block_until_ready(p)
        observe(f"--chips 4: {'sharded' if mesh else 'one-device'} engine, "
                f"{rounds} round(s): {time.perf_counter() - t0:.2f} s "
                f"including compile {clock.seconds - before:.2f} s; "
                f"losses {[round(float(x), 4) for x in m.loss]}")
        check(bool(jnp.isfinite(m.loss).all()), "non-finite loss")
        return eng, p

    _, p_one = run(None, None)
    p_one = jax.device_get(p_one)
    eng, p_shard = run("data", mesh)
    for name, tree in (("staged dataset", eng.sampler),
                       ("parameters", p_shard)):
        spread = {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}
        print(f"--chips 4: {name} held on {sorted(spread)} device(s)",
              flush=True)
        check(spread == {4}, f"{name} not replicated over the mesh")
    p_shard = jax.device_get(p_shard)
    diff = max(float(abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(p_shard), jax.tree.leaves(p_one)))
    update = max(float(abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(p_one), jax.tree.leaves(jax.device_get(params))))
    bound = SHARDED_REL_BOUND * update
    print(f"--chips 4: parameter max-abs difference sharded vs one-device "
          f"{diff:.3e} (bound {bound:.3e} = {SHARDED_REL_BOUND:g} x the "
          f"largest update {update:.3e}) after {rounds} round(s) of "
          f"{CLIENTS_PER_ROUND} clients, {CLIENTS_PER_ROUND // 4} per chip",
          flush=True)
    check(diff <= bound, f"sharded vs one-device: {diff:.3e} > {bound:.3e}")


# ------------------------------------------------------------------ main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + checks on one chip; 4: only the "
                         "sharded-cohort engine against the one-device "
                         "engine")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp

    from repro import utils                   # fails outside a checkout

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} devices "
              f"only", file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {utils.enable_compile_cache()}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    clock = CompileClock(jax)

    if args.chips == 4:
        phase_sharded(jax, jnp, clock)
    else:
        phase_train(jax, clock, dev)
        gc.collect()
        phase_appendix_a(jax, jnp)
        phase_kernels(jax, jnp)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
