"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.cco_stats import cco_stats_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.segment_sum import segment_sum_pallas


class TestCcoStatsKernel:
    @pytest.mark.parametrize("n,d", [(64, 128), (512, 256), (300, 200),
                                     (1000, 384), (128, 512), (9, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, d, dtype, rng_key):
        k1, k2 = jax.random.split(rng_key)
        zf = jax.random.normal(k1, (n, d), jnp.float32).astype(dtype)
        zg = jax.random.normal(k2, (n, d), jnp.float32).astype(dtype)
        out = cco_stats_pallas(zf, zg, block_n=128, block_d=128, interpret=True)
        expected = ref.cco_stats_ref(zf, zg)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        for k in expected:
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expected[k]),
                                       rtol=tol, atol=tol)

    @pytest.mark.parametrize("bn,bd", [(128, 128), (256, 256), (512, 128)])
    def test_block_shape_invariance(self, bn, bd, rng_key):
        zf = jax.random.normal(rng_key, (384, 256), jnp.float32)
        zg = jax.random.normal(jax.random.PRNGKey(3), (384, 256), jnp.float32)
        out = cco_stats_pallas(zf, zg, block_n=bn, block_d=bd, interpret=True)
        expected = ref.cco_stats_ref(zf, zg)
        for k in expected:
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expected[k]),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("n,d", [(64, 128), (300, 200), (9, 64)])
    def test_full_moment_set_matches_ref(self, n, d, rng_key):
        """moments="full" (the VICReg/W-MSE moment set): the two
        within-view second moments match the oracle and the five shared
        statistics are bit-identical to the default "cross" kernel."""
        k1, k2 = jax.random.split(rng_key)
        zf = jax.random.normal(k1, (n, d), jnp.float32)
        zg = jax.random.normal(k2, (n, d), jnp.float32)
        out5 = cco_stats_pallas(zf, zg, block_n=128, block_d=128,
                                interpret=True)
        out7 = cco_stats_pallas(zf, zg, block_n=128, block_d=128,
                                interpret=True, moments="full")
        expected = ref.cco_stats_ref(zf, zg, second_moments=True)
        assert set(out7) == set(expected)
        for k in out5:
            np.testing.assert_array_equal(np.asarray(out5[k]),
                                          np.asarray(out7[k]))
        for k in expected:
            np.testing.assert_allclose(np.asarray(out7[k]),
                                       np.asarray(expected[k]),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("moments", ["cross", "full"])
    def test_tpu_pipeline_semantics(self, moments, rng_key):
        """Under the TPU interpreter, which models the TPU pipeline (an
        output block is written back when the grid leaves it and never
        read again, so revisiting it on non-consecutive steps is an
        error), three d tiles still give the oracle's statistics."""
        from jax.experimental.pallas import tpu as pltpu
        k1, k2 = jax.random.split(rng_key)
        zf = jax.random.normal(k1, (256, 384), jnp.float32)
        zg = zf + 0.5 * jax.random.normal(k2, (256, 384), jnp.float32)
        with pltpu.force_tpu_interpret_mode(pltpu.InterpretParams()):
            out = cco_stats_pallas(zf, zg, block_n=128, block_d=128,
                                   moments=moments)
        expected = ref.cco_stats_ref(zf, zg,
                                     second_moments=moments == "full")
        for k in expected:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(expected[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_unknown_moment_set_rejected(self, rng_key):
        z = jax.random.normal(rng_key, (8, 8), jnp.float32)
        with pytest.raises(ValueError):
            cco_stats_pallas(z, z, interpret=True, moments="diag")

    def test_feeds_cco_loss(self, rng_key):
        """End-to-end: kernel statistics -> identical CCO loss value."""
        from repro.core import cco
        zf = jax.random.normal(rng_key, (256, 128), jnp.float32)
        zg = zf + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (256, 128))
        st_kernel = cco_stats_pallas(zf, zg, interpret=True)
        l1 = cco.cco_loss_from_stats(st_kernel, 20.0)
        l2 = cco.cco_loss(zf, zg, 20.0)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


class TestSegmentSumKernel:
    """The hierarchy's client->edge fold (kernels/segment_sum.py) vs the
    jax.ops.segment_sum oracle — random ids, weighted rows, ragged shapes
    that exercise the internal padding."""

    @pytest.mark.parametrize("k,d,e", [(64, 128, 8), (300, 96, 5),
                                       (37, 13, 3), (8, 1, 8), (512, 256, 2)])
    def test_matches_ref(self, k, d, e, rng_key):
        k1, k2, k3 = jax.random.split(rng_key, 3)
        rows = jax.random.normal(k1, (k, d), jnp.float32)
        ids = jax.random.randint(k2, (k,), 0, e)
        w = jax.random.uniform(k3, (k,), jnp.float32)
        out = segment_sum_pallas(rows, ids, e, w, interpret=True)
        expected = ref.segment_sum_ref(rows, ids, e, w)
        assert out.shape == (e, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_unweighted_and_empty_segments(self, rng_key):
        rows = jax.random.normal(rng_key, (40, 24), jnp.float32)
        ids = jnp.minimum(jnp.arange(40, dtype=jnp.int32) // 10, 2)
        out = segment_sum_pallas(rows, ids, 6, interpret=True)
        expected = ref.segment_sum_ref(rows, ids, 6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)
        # segments 3..5 receive no rows and must be exactly zero
        assert float(jnp.abs(out[3:]).max()) == 0.0

    @pytest.mark.parametrize("bk,bd", [(16, 8), (128, 128), (512, 64)])
    def test_block_shape_invariance(self, bk, bd, rng_key):
        k1, k2 = jax.random.split(rng_key)
        rows = jax.random.normal(k1, (200, 48), jnp.float32)
        ids = jax.random.randint(k2, (200,), 0, 7)
        out = segment_sum_pallas(rows, ids, 7, block_k=bk, block_d=bd,
                                 interpret=True)
        expected = ref.segment_sum_ref(rows, ids, 7)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_contiguous_fold_equals_reshape_sum(self, rng_key):
        """The hierarchy's layout: contiguous equal edges — the fold is a
        reshape-sum, the kernel must agree."""
        rows = jax.random.normal(rng_key, (64, 32), jnp.float32)
        ids = jnp.arange(64, dtype=jnp.int32) // 16
        out = segment_sum_pallas(rows, ids, 4, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(rows.reshape(4, 16, 32).sum(1)),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,h,kvh,sq,skv,dh", [
        (2, 4, 2, 128, 128, 64),
        (1, 8, 8, 256, 256, 32),
        (2, 4, 1, 64, 256, 64),     # GQA 4:1, chunked-prefill style
        (1, 2, 2, 128, 128, 128),
        (1, 16, 4, 64, 64, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, h, kvh, sq, skv, dh, dtype, rng_key):
        ks = jax.random.split(rng_key, 3)
        q = jax.random.normal(ks[0], (b, h, sq, dh), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, kvh, skv, dh), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, kvh, skv, dh), jnp.float32).astype(dtype)
        o = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                   block_kv=64, interpret=True)
        expected = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(expected, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("window", [32, 96])
    def test_sliding_window(self, window, rng_key):
        ks = jax.random.split(rng_key, 3)
        q = jax.random.normal(ks[0], (1, 4, 256, 64))
        k = jax.random.normal(ks[1], (1, 4, 256, 64))
        v = jax.random.normal(ks[2], (1, 4, 256, 64))
        o = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   block_q=64, block_kv=64, interpret=True)
        expected = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("bq,bkv", [(32, 32), (64, 128), (128, 64)])
    def test_block_shape_invariance(self, bq, bkv, rng_key):
        ks = jax.random.split(rng_key, 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64))
        k = jax.random.normal(ks[1], (1, 2, 128, 64))
        v = jax.random.normal(ks[2], (1, 2, 128, 64))
        o = flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                   block_kv=bkv, interpret=True)
        expected = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self, rng_key):
        ks = jax.random.split(rng_key, 3)
        q = jax.random.normal(ks[0], (1, 2, 64, 32))
        k = jax.random.normal(ks[1], (1, 2, 64, 32))
        v = jax.random.normal(ks[2], (1, 2, 64, 32))
        o = flash_attention_pallas(q, k, v, causal=False, block_q=32,
                                   block_kv=32, interpret=True)
        expected = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)
