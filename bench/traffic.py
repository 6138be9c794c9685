"""The one traffic generator: a cell's dataset and client partition, made
from ``--seed`` and the parameters in ``bench/traffic/<traffic>.json``.

The configuration's family (``bench/families/<family>.py``) calls a
dataset generator here with its sample shape; the partition is the
family's no concern. The program under test receives only what is made
here (per-sample leaves, labels, client index and sizes); the plain
reference (``bench/protocol.py``) reads the same host arrays.
"""
from __future__ import annotations

import numpy as np


def seed_sequence(seed: int, stream: str) -> np.random.SeedSequence:
    """An independent numpy stream per purpose, from any non-negative seed
    (seeds may exceed 32 bits)."""
    return np.random.SeedSequence([int(seed), sum(map(ord, stream))])


def jax_key_data(seed: int, stream: str) -> np.ndarray:
    """A raw threefry key (uint32[2]) for ``stream``, from the seed."""
    return seed_sequence(seed, stream).generate_state(2, np.uint32)


# -- datasets -----------------------------------------------------------------

def prototype_images(rng: np.random.Generator, num_samples: int,
                     num_classes: int, shape, noise: float):
    """Class prototypes + per-sample noise, min-max scaled to [0, 1], for
    images of ``shape`` (H, W, C).
    Returns (images (N, H*W*C) f32 rows, labels (N,) int32).

    Adapted from ``src/repro/data/synthetic.py::synthetic_labeled_images``:
    the same model of the data, drawn with a ``np.random.Generator``
    (float32 normals) and kept as (N, H*W*C) rows, the layout the program
    stages on the device."""
    width = int(np.prod(shape))
    protos = rng.standard_normal((num_classes, width), np.float32)
    labels = rng.integers(0, num_classes, num_samples)
    imgs = rng.standard_normal((num_samples, width), np.float32)
    imgs *= np.float32(noise)
    imgs += protos[labels]
    lo, hi = imgs.min(), imgs.max()
    imgs -= lo
    imgs *= np.float32(1.0 / (hi - lo + 1e-6))
    return imgs, labels.astype(np.int32)


DATASETS = {"prototype_images": prototype_images}


# -- partitions -----------------------------------------------------------------

def dirichlet(rng, labels, samples_per_client: int, alpha: float):
    """Label-skewed clients of ``samples_per_client`` samples. Only the
    paper's alpha = 0 limit is generated: each client holds one class
    (the samples of a class, in random order, cut into consecutive
    clients; a client that straddles two classes holds both)."""
    if alpha != 0:
        raise ValueError(f"dirichlet partition: only alpha = 0 is "
                         f"generated, got {alpha}")
    n = len(labels)
    order = np.lexsort((rng.random(n), labels))
    num_clients = n // samples_per_client
    index = order[:num_clients * samples_per_client].reshape(
        num_clients, samples_per_client)
    return index, np.full(num_clients, samples_per_client, np.int32)


def dirichlet_quantity(rng, labels, samples_per_client: int,
                       severity: float, sizes_seed=None):
    """Quantity skew, labels IID: client sizes ~ Dir(beta) over clients,
    beta = 10 ** (3 - 6 * severity), floored at 1 and capped at
    ``samples_per_client``; padding slots repeat the client's first index.
    With ``sizes_seed`` every seed gives each client the same size (drawn
    from that seed), and the seed only chooses the samples.

    Adapted from ``src/repro/data/partition.py::
    dirichlet_quantity_partition`` (same sizes law, vectorized)."""
    n = len(labels)
    num_clients = n // samples_per_client
    beta = 10.0 ** (3.0 - 6.0 * severity)
    size_rng = rng if sizes_seed is None else np.random.Generator(
        np.random.PCG64(seed_sequence(sizes_seed, "sizes")))
    q = size_rng.dirichlet(beta * np.ones(num_clients))
    sizes = np.clip(np.round(q * num_clients * samples_per_client), 1,
                    samples_per_client).astype(np.int32)
    perm = rng.permutation(n)[:int(sizes.sum())]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slot = np.arange(samples_per_client)[None, :]
    pos = starts[:, None] + np.minimum(slot, sizes[:, None] - 1)
    pos = np.where(slot < sizes[:, None], pos, starts[:, None])
    return perm[pos].astype(np.int64), sizes


PARTITIONS = {"dirichlet": dirichlet, "dirichlet_quantity": dirichlet_quantity}


class Traffic:
    """One cell's generated inputs."""

    def __init__(self, leaves, shapes, labels, client_index, client_sizes):
        self.leaves = leaves                  # name -> (N, elements), host
        self.shapes = shapes                  # name -> one sample's shape
        self.labels = labels                  # (N,) int32
        self.client_index = client_index      # (clients, n_pad) int64
        self.client_sizes = client_sizes      # (clients,) int32

    @property
    def num_clients(self) -> int:
        return self.client_index.shape[0]


def make_dataset(spec: dict, seed: int, **sample):
    """The traffic's dataset from the seed; ``sample`` is what the family
    says of one sample (for images, its ``shape``)."""
    d = dict(spec["dataset"])
    gen = DATASETS[d.pop("generator")]
    rng = np.random.Generator(np.random.PCG64(seed_sequence(seed, "data")))
    return gen(rng, **sample, **d)


def make_partition(spec: dict, labels, seed: int):
    p = dict(spec["partition"])
    fn = PARTITIONS[p.pop("strategy")]
    rng = np.random.Generator(np.random.PCG64(
        seed_sequence(seed, "partition")))
    return fn(rng, labels, **p)
