"""Data substrate: Dirichlet non-IID partitioning (Hsu et al. process),
partition strategies as data (PartitionSpec), stateless two-view
augmentations, federated pipeline layouts."""
import functools
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data import augment, partition, pipeline, synthetic

SET = settings(max_examples=20, deadline=None)


class TestPartition:
    def test_alpha_zero_single_class_clients(self):
        """alpha=0 (paper's non-IID): every client is single-class."""
        _, labels = synthetic.synthetic_labeled_images(2000, 10, image_size=4)
        idx = partition.dirichlet_partition(labels, 50, 8, alpha=0.0, seed=1)
        per_client_classes = [len(np.unique(labels[row])) for row in idx]
        assert np.mean(per_client_classes) < 1.5

    def test_alpha_large_is_iid_like(self):
        _, labels = synthetic.synthetic_labeled_images(4000, 10, image_size=4)
        idx = partition.dirichlet_partition(labels, 40, 16, alpha=1000.0, seed=1)
        per_client_classes = [len(np.unique(labels[row])) for row in idx]
        assert np.mean(per_client_classes) > 5

    def test_no_duplicate_samples(self):
        _, labels = synthetic.synthetic_labeled_images(1000, 5, image_size=4)
        idx = partition.dirichlet_partition(labels, 20, 10, alpha=1.0, seed=0)
        flat = idx.reshape(-1)
        assert len(np.unique(flat)) == len(flat)

    def test_iid_partition_shapes(self):
        idx = partition.iid_partition(500, 25, 4, seed=3)
        assert idx.shape == (25, 4)
        assert len(np.unique(idx.reshape(-1))) == 100


class TestPartitionSpec:
    """Strategies-as-data API: registry, severity axis, conservation."""

    def _labels(self, n=900, c=6):
        _, labels = synthetic.synthetic_labeled_images(n, c, image_size=4)
        return labels

    def test_registry_lists_all_strategies(self):
        assert set(partition.PARTITIONS) >= {
            "iid", "uniform", "label", "dirichlet", "dirichlet_quantity"}
        for name in partition.PARTITIONS:
            assert callable(partition.get_partition(name))
        with pytest.raises(ValueError, match="unknown partition"):
            partition.get_partition("no_such_strategy")

    def test_register_partition_extends_registry(self):
        def halves(labels, num_clients, samples_per_client, severity,
                   seed=0):
            return partition.iid_partition(
                len(np.asarray(labels)), num_clients, samples_per_client,
                seed)
        partition.register_partition("test_halves", halves)
        try:
            assert "test_halves" in partition.PARTITIONS
            idx, sizes = partition.build_partition(
                partition.PartitionSpec("test_halves", 0.5),
                self._labels(), num_clients=10, samples_per_client=3)
            assert idx.shape == (10, 3)
            assert (sizes == 3).all()
        finally:
            partition._REGISTRY.pop("test_halves")
            partition.PARTITIONS = tuple(partition._REGISTRY)

    @SET
    @given(strategy=st.sampled_from(
        ["iid", "uniform", "label", "dirichlet", "dirichlet_quantity"]),
        severity=st.floats(0.0, 1.0), seed=st.integers(0, 2**10))
    def test_sample_conservation(self, strategy, severity, seed):
        """Every strategy: each assigned (non-padding) slot holds a
        distinct dataset index — no sample duplicated or invented."""
        labels = self._labels()
        idx, sizes = partition.build_partition(
            partition.PartitionSpec(strategy, severity), labels,
            num_clients=30, samples_per_client=6, seed=seed)
        assert idx.shape == (30, 6) and sizes.shape == (30,)
        assert (1 <= sizes).all() and (sizes <= 6).all()
        valid = np.concatenate(
            [idx[k, : sizes[k]] for k in range(30)])
        assert len(np.unique(valid)) == len(valid)
        assert valid.min() >= 0 and valid.max() < len(labels)

    @SET
    @given(seed=st.integers(0, 2**10))
    def test_label_dominance_monotone_in_severity(self, seed):
        """The label-skew metric rises with severity for the skewing
        strategies and stays flat for the controls."""
        labels = self._labels()
        for strategy in ("label", "dirichlet"):
            doms = []
            for sev in (0.0, 0.5, 1.0):
                idx, sizes = partition.build_partition(
                    partition.PartitionSpec(strategy, sev), labels,
                    num_clients=30, samples_per_client=6, seed=seed)
                doms.append(partition.label_dominance(labels, idx, sizes))
            assert doms[0] <= doms[1] <= doms[2], (strategy, doms)
            assert doms[2] > doms[0] + 0.3, (strategy, doms)
        # uniform: severity-flat, maximally homogeneous
        u0, _ = partition.build_partition(
            partition.PartitionSpec("uniform", 0.0), labels,
            num_clients=30, samples_per_client=6, seed=seed)
        u1, _ = partition.build_partition(
            partition.PartitionSpec("uniform", 1.0), labels,
            num_clients=30, samples_per_client=6, seed=seed)
        np.testing.assert_array_equal(u0, u1)

    def test_quantity_skew_severity_spreads_sizes(self):
        labels = self._labels()
        _, s0 = partition.build_partition(
            partition.PartitionSpec("dirichlet_quantity", 0.0), labels,
            num_clients=30, samples_per_client=6, seed=0)
        _, s1 = partition.build_partition(
            partition.PartitionSpec("dirichlet_quantity", 1.0), labels,
            num_clients=30, samples_per_client=6, seed=0)
        assert np.std(s1) > np.std(s0)

    def test_infeasible_partition_raises_with_bound(self):
        labels = self._labels(n=100)
        with pytest.raises(ValueError, match="supports at most 16 clients"):
            partition.dirichlet_partition(labels, 20, 6, alpha=1.0)
        with pytest.raises(ValueError, match="infeasible"):
            partition.build_partition(
                partition.PartitionSpec("label", 1.0), labels,
                num_clients=101, samples_per_client=1)

    def test_severity_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="severity"):
            partition.build_partition(
                partition.PartitionSpec("label", 1.5), self._labels(),
                num_clients=4, samples_per_client=2)

    def test_alpha_override_dirichlet_only(self):
        with pytest.raises(ValueError, match="'dirichlet' strategy only"):
            partition.build_partition(
                partition.PartitionSpec("label", alpha=3.0), self._labels(),
                num_clients=4, samples_per_client=2)

    def test_severity_alpha_anchors(self):
        assert partition.severity_to_alpha(0.0) == pytest.approx(1000.0)
        assert partition.severity_to_alpha(1.0) == pytest.approx(1e-3)
        assert partition.severity_to_classes(0.0, 10) == 10
        assert partition.severity_to_classes(1.0, 10) == 1

    def test_deprecated_alpha_alias_bit_identical(self):
        """build(alpha=...) == build(partition=PartitionSpec(...)) — the
        historical client assignment survives the API redesign exactly."""
        imgs, labels = synthetic.synthetic_labeled_images(300, 5,
                                                          image_size=4)
        for alpha in (0.0, 0.5, 1e7):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                old = pipeline.FederatedDataset.build(
                    {"images": imgs}, labels, num_clients=20,
                    samples_per_client=4, alpha=alpha, seed=3)
            new = pipeline.FederatedDataset.build(
                {"images": imgs}, labels, num_clients=20,
                samples_per_client=4,
                partition=partition.PartitionSpec("dirichlet", alpha=alpha),
                seed=3)
            np.testing.assert_array_equal(old.client_index,
                                          new.client_index)
            np.testing.assert_array_equal(old.client_sizes,
                                          new.client_sizes)

    def test_deprecated_alpha_warns_and_both_rejected(self):
        imgs, labels = synthetic.synthetic_labeled_images(100, 4,
                                                          image_size=4)
        with pytest.warns(DeprecationWarning):
            pipeline.FederatedDataset.build(
                {"images": imgs}, labels, num_clients=10,
                samples_per_client=2, alpha=0.0)
        with pytest.raises(ValueError, match="not both"):
            pipeline.FederatedDataset.build(
                {"images": imgs}, labels, num_clients=10,
                samples_per_client=2, alpha=0.0,
                partition=partition.PartitionSpec("iid"))
        with pytest.raises(TypeError):
            pipeline.FederatedDataset.build(
                {"images": imgs}, labels, num_clients=10,
                samples_per_client=2)

    def test_variable_sizes_ride_the_samplers(self, rng_key):
        """dirichlet_quantity sizes flow through round_batch AND the
        in-scan sampler (pad slots masked downstream by sizes)."""
        imgs, labels = synthetic.synthetic_labeled_images(300, 5,
                                                          image_size=4)
        ds = pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=30, samples_per_client=4,
            partition=partition.PartitionSpec("dirichlet_quantity", 0.9),
            seed=0)
        assert ds.client_sizes.min() >= 1
        assert (ds.client_sizes <= 4).any()
        _, sizes_host = ds.round_batch(rng_key, clients_per_round=8)
        sampler = ds.make_round_sampler(8)
        k_sel, k_aug = jax.random.split(rng_key)
        _, sizes_scan = sampler(k_sel, k_aug)
        assert sizes_host.shape == (8,) and sizes_scan.shape == (8,)
        assert int(jnp.max(sizes_scan)) <= 4


def _oracle_crop(img, top, left, flip, crop_frac):
    """The crop by slice and index gathers that ``crop_resize_flip``
    replaced, kept as its reference."""
    h, w, c = img.shape
    ch, cw = int(h * crop_frac), int(w * crop_frac)
    crop = jax.lax.dynamic_slice(img, (top, left, 0), (ch, cw, c))
    ridx = (jnp.arange(h) * ch // h).astype(jnp.int32)
    cidx = (jnp.arange(w) * cw // w).astype(jnp.int32)
    out = crop[ridx][:, cidx]
    return jnp.where(flip, out[:, ::-1], out)


def _oracle_augment(key, img, crop_frac=0.8):
    kc, kf, kb, kcon = jax.random.split(key, 4)
    h, w, c = img.shape
    ch, cw = int(h * crop_frac), int(w * crop_frac)
    top = jax.random.randint(kc, (), 0, h - ch + 1)
    left = jax.random.randint(kc, (), 0, w - cw + 1)
    out = _oracle_crop(img, top, left, jax.random.bernoulli(kf), crop_frac)
    brightness = 1.0 + 0.4 * (jax.random.uniform(kb) - 0.5)
    contrast = 1.0 + 0.4 * (jax.random.uniform(kcon) - 0.5)
    mean = out.mean()
    return jnp.clip((out - mean) * contrast + mean * brightness, 0.0, 1.0)


IMAGE_SHAPES = [(32, 32, 3), (16, 16, 3), (24, 16, 1)]


class TestAugment:
    @pytest.mark.parametrize("flip", [False, True])
    @pytest.mark.parametrize("crop_frac", [0.5, 0.8, 1.0])
    @pytest.mark.parametrize("shape", IMAGE_SHAPES)
    def test_crop_matches_slice_oracle_bitwise(self, shape, crop_frac, flip):
        """The one-hot crop equals the slice-and-gather crop bit for bit
        at every (top, left) offset."""
        h, w, _ = shape
        ch, cw = int(h * crop_frac), int(w * crop_frac)
        tops, lefts = np.meshgrid(np.arange(h - ch + 1), np.arange(w - cw + 1),
                                  indexing="ij")
        tops, lefts = tops.ravel(), lefts.ravel()
        imgs = jax.random.uniform(jax.random.PRNGKey(len(tops)),
                                  (len(tops),) + shape)
        flips = jnp.full(len(tops), flip)
        new = jax.jit(jax.vmap(functools.partial(
            augment.crop_resize_flip, crop_frac=crop_frac)))(
                imgs, tops, lefts, flips)
        old = jax.jit(jax.vmap(functools.partial(
            _oracle_crop, crop_frac=crop_frac)))(imgs, tops, lefts, flips)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

    @pytest.mark.parametrize("shape", IMAGE_SHAPES)
    def test_two_views_match_oracle(self, shape):
        """Whole views: the same keys give the oracle's views up to the
        mean's reduction order."""
        keys = jax.random.split(jax.random.PRNGKey(7), 64)
        imgs = jax.random.uniform(jax.random.PRNGKey(8), (64,) + shape)
        new = jax.jit(jax.vmap(augment.two_views_image))(keys, imgs)

        def oracle(key, img):
            k1, k2 = jax.random.split(key)
            return _oracle_augment(k1, img), _oracle_augment(k2, img)

        old = jax.jit(jax.vmap(oracle))(keys, imgs)
        for a, b in zip(new, old):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)

    def test_batched_views_have_no_gather_or_dynamic_slice(self):
        """A batched slice or gather is what XLA on the TPU turns into a
        serial per-sample loop; the compiled views hold neither."""
        hlo = jax.jit(jax.vmap(augment.two_views_image)).lower(
            jax.ShapeDtypeStruct((512, 2), jnp.uint32),
            jax.ShapeDtypeStruct((512, 32, 32, 3), jnp.float32),
        ).compile().as_text()
        for op in ("gather", "dynamic-slice", "dynamic-update-slice"):
            assert not re.search(rf"\s{op}\(", hlo), op

    def test_stateless_determinism(self, rng_key):
        img = jax.random.uniform(rng_key, (16, 16, 3))
        a1 = augment.augment_image(jax.random.PRNGKey(5), img)
        a2 = augment.augment_image(jax.random.PRNGKey(5), img)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

    def test_two_views_differ(self, rng_key):
        img = jax.random.uniform(rng_key, (16, 16, 3))
        v1, v2 = augment.two_views_image(rng_key, img)
        assert float(jnp.max(jnp.abs(v1 - v2))) > 1e-3
        assert v1.shape == img.shape

    def test_token_augment_preserves_shape_and_vocab(self, rng_key):
        toks = jax.random.randint(rng_key, (32,), 0, 100)
        v1, v2 = augment.two_views_tokens(rng_key, toks, vocab=100)
        assert v1.shape == toks.shape
        assert int(v1.max()) < 100 and int(v1.min()) >= 0
        assert not np.array_equal(np.asarray(v1), np.asarray(v2))


class TestPipeline:
    def _ds(self):
        imgs, labels = synthetic.synthetic_labeled_images(400, 5, image_size=8)
        return pipeline.FederatedDataset.build(
            {"images": imgs}, labels, num_clients=40, samples_per_client=4,
            alpha=0.0, seed=0)

    def test_round_batch_layout(self, rng_key):
        ds = self._ds()
        batch, sizes = ds.round_batch(rng_key, clients_per_round=8)
        assert batch["v1"].shape == (8, 4, 8, 8, 3)
        assert batch["v2"].shape == (8, 4, 8, 8, 3)
        assert sizes.shape == (8,)

    def test_flat_round_batch(self, rng_key):
        ds = self._ds()
        flat, sizes = ds.flat_round_batch(rng_key, clients_per_round=8)
        assert flat["v1"].shape == (32, 8, 8, 3)

    def test_token_dataset(self, rng_key):
        toks, labels = synthetic.synthetic_labeled_tokens(200, 4, 16, vocab=64)
        ds = pipeline.FederatedDataset.build(
            {"tokens": toks}, labels, num_clients=20, samples_per_client=2,
            alpha=0.0, seed=0, vocab=64)
        batch, sizes = ds.round_batch(rng_key, clients_per_round=4)
        assert batch["v1"].shape == (4, 2, 16)
        assert batch["v1"].dtype == jnp.int32


class TestSynthetic:
    def test_labels_linearly_separable_in_pixel_space(self):
        """The synthetic generator must carry class signal (probe sanity)."""
        from repro.core import eval as eval_lib
        imgs, labels = synthetic.synthetic_labeled_images(600, 5, image_size=8,
                                                          noise=0.2)
        z = jnp.asarray(imgs.reshape(600, -1))
        y = jnp.asarray(labels)
        acc = eval_lib.ridge_linear_probe(z[:400], y[:400], z[400:], y[400:], 5)
        assert float(acc) > 0.9
