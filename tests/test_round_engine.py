"""Scan-compiled round engine (repro.core.round_engine):

  * scan-of-N-rounds == N Python-driven fed_sim rounds (same fold_in keys);
  * segment chunking is a pure implementation detail (chunk=3 == chunk=7);
  * all five algorithm bodies run and train;
  * phase-1 aggregate stats through the Pallas kernel == the jnp path;
  * in-scan sampler == host-driven FederatedDataset.round_batch;
  * chunked metrics streaming + periodic checkpointing;
  * sharded-cohort DCCO == single-device DCCO on a forced 2-device CPU mesh
    (subprocess, --xla_force_host_platform_device_count).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import utils
from repro.core import fed_sim, round_engine
from repro.data import partition, pipeline, synthetic
from repro.optim import optimizers as opt_lib

LAM = 5.0


@pytest.fixture(scope="module")
def toy():
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (10, 16)) * 0.3,
              "w2": jax.random.normal(jax.random.PRNGKey(7), (16, 6)) * 0.3}

    def apply(p, batch):
        def enc(x):
            return jnp.tanh(x @ p["w1"]) @ p["w2"]
        return enc(batch["v1"]), enc(batch["v2"])

    pool = {"v1": jax.random.normal(jax.random.PRNGKey(1), (20, 3, 10)),
            "v2": jax.random.normal(jax.random.PRNGKey(2), (20, 3, 10))}

    def sampler(k_sel, k_aug):
        sel = jax.random.choice(k_sel, 20, (6,), replace=False)
        return (jax.tree.map(lambda x: x[sel], pool),
                jnp.full((6,), 3, jnp.int32))

    return params, apply, sampler


def _run_python_loop(params, apply, sampler, opt, rng, rounds, **round_kw):
    """The reference: one fed_sim round per Python dispatch, keys derived
    exactly like the engine derives them in-scan."""
    p, st = params, opt.init(params)
    losses = []
    for r in range(rounds):
        k_sel, k_aug = jax.random.split(jax.random.fold_in(rng, r))
        batch, sizes = sampler(k_sel, k_aug)
        p, st, m = fed_sim.dcco_round(apply, p, st, opt, batch, sizes,
                                      lam=LAM, **round_kw)
        losses.append(float(m.loss))
    return p, st, np.asarray(losses)


class TestScanEquivalence:
    def test_scan_equals_python_loop(self, toy):
        params, apply, sampler = toy
        opt = opt_lib.adam(1e-2)
        rng = jax.random.PRNGKey(3)
        cfg = round_engine.EngineConfig(algorithm="dcco", lam=LAM,
                                        chunk_rounds=8)
        eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
        pe, se, me = eng.run(params, opt.init(params), rng, 8)
        pl, sl, losses = _run_python_loop(params, apply, sampler, opt, rng, 8)
        assert utils.tree_max_abs_diff(pe, pl) < 1e-6
        np.testing.assert_allclose(np.asarray(me.loss), losses,
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_chunking_is_invisible(self, toy):
        params, apply, sampler = toy
        opt = opt_lib.sgd(0.1)
        rng = jax.random.PRNGKey(5)
        outs = []
        for chunk in (3, 7):
            cfg = round_engine.EngineConfig(algorithm="dcco", lam=LAM,
                                            chunk_rounds=chunk)
            eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
            outs.append(eng.run(params, opt.init(params), rng, 7))
        assert utils.tree_max_abs_diff(outs[0][0], outs[1][0]) < 1e-6
        np.testing.assert_allclose(np.asarray(outs[0][2].loss),
                                   np.asarray(outs[1][2].loss),
                                   rtol=1e-6, atol=1e-7)

    def test_start_round_offsets_the_rng_stream(self, toy):
        """Resume semantics: running [0, 4) then [4, 8) == running [0, 8)."""
        params, apply, sampler = toy
        opt = opt_lib.sgd(0.1)
        rng = jax.random.PRNGKey(9)
        cfg = round_engine.EngineConfig(algorithm="dcco", lam=LAM,
                                        chunk_rounds=4)
        eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
        p1, s1, _ = eng.run(params, opt.init(params), rng, 4)
        p1, s1, _ = eng.run(p1, s1, rng, 4, start_round=4)
        p2, s2, _ = eng.run(params, opt.init(params), rng, 8)
        assert utils.tree_max_abs_diff(p1, p2) < 1e-6


class TestAlgorithmBodies:
    @pytest.mark.parametrize("algorithm", round_engine.ALGORITHMS)
    def test_runs_and_trains(self, toy, algorithm):
        params, apply, sampler = toy
        opt = opt_lib.adam(1e-2)
        cfg = round_engine.EngineConfig(algorithm=algorithm, lam=LAM,
                                        chunk_rounds=3)
        eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
        p, s, m = eng.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
        assert m.loss.shape == (6,)
        assert bool(jnp.isfinite(m.loss).all())
        assert utils.tree_max_abs_diff(p, params) > 0.0

    def test_dcco_equals_centralized_body(self, toy):
        """Appendix A inside the engine: the dcco and centralized bodies
        produce the same trajectory at client_lr=1, one local step."""
        params, apply, sampler = toy
        opt = opt_lib.sgd(0.05)
        outs = {}
        for algorithm in ("dcco", "centralized"):
            cfg = round_engine.EngineConfig(algorithm=algorithm, lam=LAM,
                                            client_lr=1.0, chunk_rounds=4)
            eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
            outs[algorithm] = eng.run(params, opt.init(params),
                                      jax.random.PRNGKey(3), 4)
        assert utils.tree_max_abs_diff(outs["dcco"][0],
                                       outs["centralized"][0]) < 1e-5

    def test_unknown_algorithm_rejected(self, toy):
        params, apply, sampler = toy
        with pytest.raises(ValueError):
            round_engine.make_round_body(
                apply, opt_lib.sgd(0.1),
                round_engine.EngineConfig(algorithm="fedprox"))


class TestKernelStatsRouting:
    @pytest.mark.slow
    def test_pallas_agg_stats_matches_jnp(self, toy):
        params, apply, sampler = toy
        opt = opt_lib.adam(1e-2)
        rng = jax.random.PRNGKey(3)
        outs = {}
        for kernel in ("off", "interpret"):
            cfg = round_engine.EngineConfig(algorithm="dcco", lam=LAM,
                                            chunk_rounds=4,
                                            stats_kernel=kernel)
            eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
            outs[kernel] = eng.run(params, opt.init(params), rng, 4)
        assert utils.tree_max_abs_diff(outs["off"][0],
                                       outs["interpret"][0]) < 1e-5
        np.testing.assert_allclose(np.asarray(outs["off"][2].loss),
                                   np.asarray(outs["interpret"][2].loss),
                                   rtol=1e-4)


class TestInScanSampler:
    def test_sampler_matches_round_batch(self):
        imgs, labels = synthetic.synthetic_labeled_images(60, 3, image_size=8,
                                                          noise=0.5, seed=1)
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=20, samples_per_client=2,
            alpha=0.0, seed=0)
        sampler = ds.make_round_sampler(5)
        key = jax.random.PRNGKey(11)
        ref_batch, ref_sizes = ds.round_batch(key, 5)
        batch, sizes = jax.jit(sampler)(*jax.random.split(key))
        assert utils.tree_max_abs_diff(batch, ref_batch) < 1e-6
        np.testing.assert_array_equal(np.asarray(sizes), np.asarray(ref_sizes))

    @pytest.mark.parametrize("kind", ["round", "async", "streaming"])
    def test_staged_argument_matches_closure(self, kind):
        """A dataset sampler passed to a jitted function as an argument
        (what the engine's segment does: its leaves are the staged arrays)
        emits batches bit-identical to the same sampler closed over as a
        constant, for the same keys."""
        imgs, labels = synthetic.synthetic_labeled_images(80, 4, image_size=8,
                                                          noise=0.5, seed=2)
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=20,
            samples_per_client=4,
            partition=partition.PartitionSpec(
                "dirichlet_quantity", severity=0.7), seed=0)
        sampler = {"round": lambda: ds.make_round_sampler(6),
                   "async": lambda: ds.make_async_round_sampler(6),
                   "streaming": lambda: ds.make_streaming_sampler(6, 2)}[kind]()
        k_sel, k_aug = jax.random.split(jax.random.PRNGKey(5))

        def draw(s):
            if kind != "streaming":
                return s(k_sel, k_aug)
            state = s.prepare(k_sel, k_aug)
            return ([s.sample_chunk(state, c) for c in range(s.num_chunks)],
                    s.cohort_sizes(k_sel))

        assert jax.tree.leaves(sampler), "the staged arrays are not leaves"
        closure = jax.jit(lambda: draw(sampler))()
        argument = jax.jit(draw)(sampler)
        for a, b in zip(jax.tree.leaves(closure), jax.tree.leaves(argument)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_segment_program_holds_no_dataset_constants(self):
        """The staged dataset is an argument of the jitted segment: its
        lowered text stays kilobytes at 2,000 32x32x3 images (24.6 MB of
        pixels, which as closure constants were ~49 MB of program text)."""
        imgs, labels = synthetic.synthetic_labeled_images(2000, 10,
                                                          image_size=32)
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=1000, samples_per_client=2,
            partition=partition.PartitionSpec("iid"), seed=0)
        params = {"w": jnp.full((32 * 32 * 3, 8), 0.01)}

        def apply(p, batch):
            def enc(x):
                return x.reshape(x.shape[0], -1) @ p["w"]
            return enc(batch["v1"]), enc(batch["v2"])

        opt = opt_lib.sgd(0.1)
        eng = round_engine.RoundEngine(
            apply, opt, ds.make_round_sampler(8),
            round_engine.EngineConfig(lam=LAM, chunk_rounds=2))
        carry = round_engine.EngineCarry(params, opt.init(params),
                                         jax.random.PRNGKey(0))
        text = eng._segment_fn(2).lower(
            carry, jnp.asarray(0, jnp.int32), eng.sampler).as_text()
        assert len(text) < 1_000_000, len(text)


class TestStreamingAndCheckpoint:
    def test_segments_stream_and_checkpoint(self, toy, tmp_path):
        from repro.checkpoint import restore_checkpoint
        params, apply, sampler = toy
        opt = opt_lib.adam(1e-2)
        cfg = round_engine.EngineConfig(algorithm="dcco", lam=LAM,
                                        chunk_rounds=2)
        eng = round_engine.RoundEngine(apply, opt, sampler, cfg)
        seen = []
        p, s, m = eng.run(
            params, opt.init(params), jax.random.PRNGKey(3), 6,
            on_segment=lambda end, carry, seg: seen.append((end, seg.loss.shape)),
            ckpt_dir=str(tmp_path), ckpt_every=2, ckpt_name="eng")
        assert seen == [(2, (2,)), (4, (2,)), (6, (2,))]
        assert m.loss.shape == (6,) and m.encoding_std.shape == (6,)
        blob, step = restore_checkpoint(str(tmp_path / "eng.msgpack"),
                                        {"params": params, "opt": opt.init(params)})
        assert step == 6
        assert utils.tree_max_abs_diff(blob["params"], p) < 1e-7


_SHARDED_SCRIPT = """
import jax, jax.numpy as jnp
assert jax.device_count() == 2, jax.device_count()
from repro import utils
from repro.core import fed_sim, round_engine
from repro.optim import optimizers as opt_lib

key = jax.random.PRNGKey(0)
params = {"w1": jax.random.normal(key, (10, 16)) * 0.3,
          "w2": jax.random.normal(jax.random.PRNGKey(7), (16, 6)) * 0.3}
def apply(p, batch):
    enc = lambda x: jnp.tanh(x @ p["w1"]) @ p["w2"]
    return enc(batch["v1"]), enc(batch["v2"])
k1, k2 = jax.random.split(key)
data = {"v1": jax.random.normal(k1, (8, 3, 10)),
        "v2": jax.random.normal(k2, (8, 3, 10))}
sizes = jnp.array([3, 1, 2, 3, 3, 2, 1, 3], jnp.int32)
mesh = jax.make_mesh((2, 1), ("data", "model"))
opt = opt_lib.adam(1e-2)
p1, s1, m1 = fed_sim.dcco_round(apply, params, opt.init(params), opt,
                                data, sizes, lam=5.0)
p2, s2, m2 = round_engine.dcco_round_sharded(apply, params, opt.init(params),
                                             opt, data, sizes, mesh, lam=5.0)
assert utils.tree_max_abs_diff(p1, p2) < 1e-6
assert abs(float(m1.loss) - float(m2.loss)) < 1e-5
assert abs(float(m1.encoding_std) - float(m2.encoding_std)) < 1e-6

# channels through the sharded psum body (repro.comm): dense == legacy,
# dropout(p=0) == dense bitwise, int8 runs and accounts bytes
from repro import comm
ck = jax.random.PRNGKey(42)
pd, sd, md = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    channel=comm.DenseChannel(), channel_key=ck)
assert utils.tree_max_abs_diff(p2, pd) < 1e-6
assert float(md.wire_bytes) > 0
p0d, s0d, m0d = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    channel=comm.DropoutChannel(0.0), channel_key=ck)
assert utils.tree_max_abs_diff(pd, p0d) == 0.0
pq, sq, mq = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    channel=comm.QuantizedChannel(8), channel_key=ck)
assert float(mq.wire_bytes) < float(md.wire_bytes) / 3
assert abs(float(mq.loss) - float(m1.loss)) < 0.5

# and scan-compiled: the engine with cohort_axis on the 2-device mesh
def sampler(k_sel, k_aug):
    return data, sizes
cfg = round_engine.EngineConfig(algorithm="dcco", lam=5.0, chunk_rounds=3,
                                cohort_axis="data")
eng = round_engine.RoundEngine(apply, opt, sampler, cfg, mesh=mesh)
pe, se, me = eng.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
cfg1 = round_engine.EngineConfig(algorithm="dcco", lam=5.0, chunk_rounds=3)
eng1 = round_engine.RoundEngine(apply, opt, sampler, cfg1)
p1, s1, m1 = eng1.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
assert utils.tree_max_abs_diff(pe, p1) < 1e-5

# sharded engine with a dropout channel: compiles, trains, accounts bytes
cfg2 = round_engine.EngineConfig(algorithm="dcco", lam=5.0, chunk_rounds=3,
                                 cohort_axis="data",
                                 channel=comm.DropoutChannel(0.3))
eng2 = round_engine.RoundEngine(apply, opt, sampler, cfg2, mesh=mesh)
pc, sc, mc = eng2.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
assert mc.wire_bytes.shape == (6,)
assert bool(jnp.isfinite(mc.loss).all())

# SCAFFOLD through the sharded psum body == single-device scaffold round:
# slot variates shard with the client axis, the variate-delta average is
# one more psum, the server variate stays replicated
from repro.server import scaffold_init
d0 = scaffold_init(params, 8)
ps1, ss1, ds1, ms1 = fed_sim.dcco_round(
    apply, params, opt.init(params), opt, data, sizes, lam=5.0,
    client_lr=0.1, local_steps=2, scaffold_state=d0)
ps2, ss2, ds2, ms2 = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    client_lr=0.1, local_steps=2, scaffold_state=d0)
assert utils.tree_max_abs_diff(ps1, ps2) < 1e-6
# variates scale like grad/(L*lr) (~5x the gradients here), so the psum
# reassociation error is correspondingly larger than on the params
assert utils.tree_max_abs_diff(ds1.c, ds2.c) < 1e-4
assert utils.tree_max_abs_diff(ds1.c_slots, ds2.c_slots) < 1e-3
# variate uplink is accounted when a channel is present
pw, sw, dw, mw = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    client_lr=0.1, local_steps=2, scaffold_state=d0,
    channel=comm.DenseChannel(), channel_key=ck)
assert float(mw.wire_bytes) > float(md.wire_bytes)
# sharded engine with scaffold in the scan carry; client_lr small enough
# that the variate dynamics are stable on this toy (a divergent trajectory
# would amplify benign psum reassociation noise into spurious mismatches)
cfg3 = round_engine.EngineConfig(algorithm="dcco", lam=5.0, chunk_rounds=3,
                                 cohort_axis="data", client_lr=0.03,
                                 local_steps=2, scaffold=True)
eng3 = round_engine.RoundEngine(apply, opt, sampler, cfg3, mesh=mesh)
pe3, se3, me3 = eng3.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
assert bool(jnp.isfinite(me3.loss).all())
cfg4 = round_engine.EngineConfig(algorithm="dcco", lam=5.0, chunk_rounds=3,
                                 client_lr=0.03, local_steps=2, scaffold=True)
eng4 = round_engine.RoundEngine(apply, opt, sampler, cfg4)
pe4, se4, me4 = eng4.run(params, opt.init(params), jax.random.PRNGKey(3), 6)
assert utils.tree_max_abs_diff(pe3, pe4) < 1e-5

# objective-parametric sharded round: D-VICReg through the 2-device psum
# body == the single-device stats_round (the 7-stat dict psums per key),
# and its channel-routed wire costs more bytes than DCCO's 5-stat dict
# hierarchical aggregation on the mesh (repro.hierarchy): 4 edges over 2
# shards -> each device folds its 4 clients into 2 local edges with the
# segment-sum kernel, the psum is the edge->server hop. Dense-dense
# collapses to the flat dense sharded result bitwise; an int8 client hop
# runs the real tree and accounts both hops' bytes.
from repro import hierarchy
ph0, sh0, mh0 = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    channel=hierarchy.HierarchicalChannel(4), channel_key=ck)
assert utils.tree_max_abs_diff(pd, ph0) == 0.0
phq, shq, mhq = round_engine.dcco_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
    channel=hierarchy.HierarchicalChannel(
        4, client_channel=comm.QuantizedChannel(8), fold_impl="interpret"),
    channel_key=ck)
assert bool(jnp.isfinite(mhq.loss))
assert float(mhq.wire_bytes) > float(mq.wire_bytes)  # + edge hop payloads
# misaligned edges (1 edge on 2 shards) are refused loudly
try:
    round_engine.dcco_round_sharded(
        apply, params, opt.init(params), opt, data, sizes, mesh, lam=5.0,
        channel=hierarchy.HierarchicalChannel(1, collapse_ideal=False),
        channel_key=ck)
    raise AssertionError("misaligned edges were not refused")
except ValueError as e:
    assert "align" in str(e)

from repro.objectives import get_objective
obj = get_objective("dvicreg")
pv1, sv1, mv1 = fed_sim.stats_round(apply, params, opt.init(params), opt,
                                    data, sizes, objective=obj)
pv2, sv2, mv2 = round_engine.stats_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, objective=obj)
assert utils.tree_max_abs_diff(pv1, pv2) < 1e-6
assert abs(float(mv1.loss) - float(mv2.loss)) < 1e-5
pv3, sv3, mv3 = round_engine.stats_round_sharded(
    apply, params, opt.init(params), opt, data, sizes, mesh, objective=obj,
    channel=comm.DenseChannel(), channel_key=ck)
assert float(mv3.wire_bytes) > float(md.wire_bytes)
print("SHARDED_OK")
"""


class TestShardedCohort:
    @pytest.mark.slow
    def test_two_device_mesh_matches_single_device(self):
        """Runs in a subprocess: the host-platform device count must be
        forced before jax initializes, which has already happened here."""
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2").strip(),
            "PYTHONPATH": os.pathsep.join(
                [os.path.join(os.path.dirname(__file__), "..", "src"),
                 env.get("PYTHONPATH", "")]).rstrip(os.pathsep),
        })
        out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                             env=env, capture_output=True, text=True,
                             timeout=420)
        assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
        assert "SHARDED_OK" in out.stdout
