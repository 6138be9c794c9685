"""Federated data pipeline: owns the client partition and emits per-round
batches in the (K, n, ...) layout expected by repro.core.fed_sim, or the
flat (N, ...) layout expected by the pod-scale fused step.
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.data import augment
from repro.data import partition as partition_lib


class FederatedDataset:
    """Wraps (data, labels) + a client partition.

    data: dict of np arrays with leading N (e.g. {"images": ...} or
    {"tokens": ...}); client_index: (num_clients, samples_per_client) int;
    client_sizes: (num_clients,) valid-sample counts — rows of
    client_index beyond a client's size are padding (masked out of every
    stats/loss computation downstream), which is how quantity-skewed
    partitions (``PartitionSpec("dirichlet_quantity", ...)``) and the
    paper's variable-size DERM clients are carried.
    """

    def __init__(self, data: Dict[str, np.ndarray], labels: np.ndarray,
                 client_index: np.ndarray, vocab: int = 0,
                 client_sizes: Optional[np.ndarray] = None):
        self.data = data
        self.labels = labels
        self.client_index = client_index
        self.vocab = vocab
        if client_sizes is None:
            client_sizes = np.full((client_index.shape[0],),
                                   client_index.shape[1], np.int64)
        self.client_sizes = np.asarray(client_sizes, np.int64)
        if self.client_sizes.shape != (client_index.shape[0],):
            raise ValueError(
                f"client_sizes shape {self.client_sizes.shape} does not "
                f"match {client_index.shape[0]} clients")
        self._samplers: Dict[int, object] = {}
        self._staged = None      # device-resident (data, index, sizes)

    @property
    def num_clients(self) -> int:
        return self.client_index.shape[0]

    @property
    def samples_per_client(self) -> int:
        return self.client_index.shape[1]

    @classmethod
    def build(cls, data, labels, *, num_clients, samples_per_client,
              partition=None, alpha: float = None,
              seed: int = 0, vocab: int = 0):
        """The one construction path: cut the client partition a
        :class:`repro.data.partition.PartitionSpec` describes.

        ``partition=PartitionSpec(strategy, severity)`` selects any
        registered strategy (iid / uniform / label / dirichlet /
        dirichlet_quantity, see :mod:`repro.data.partition`).

        ``alpha=`` is the deprecated pre-PartitionSpec spelling; it maps
        onto ``PartitionSpec("dirichlet", alpha=alpha)`` (alpha >= 1e6
        still means IID) and produces a bit-identical client assignment
        for existing seeds — tested ``==`` — so old configs, regression
        baselines, and resume streams are unaffected.
        """
        if partition is not None and alpha is not None:
            raise ValueError(
                "pass partition=PartitionSpec(...) or the deprecated "
                "alpha=, not both")
        if partition is None:
            if alpha is None:
                raise TypeError(
                    "FederatedDataset.build needs "
                    "partition=PartitionSpec(...) (or the deprecated "
                    "alpha=)")
            warnings.warn(
                "FederatedDataset.build(alpha=...) is deprecated; use "
                "partition=PartitionSpec('dirichlet', alpha=alpha) or a "
                "severity-mapped PartitionSpec",
                DeprecationWarning, stacklevel=2)
            partition = partition_lib.PartitionSpec(
                "dirichlet", alpha=float(alpha))
        idx, sizes = partition_lib.build_partition(
            partition, labels, num_clients=num_clients,
            samples_per_client=samples_per_client, seed=seed)
        return cls(data, labels, idx, vocab=vocab, client_sizes=sizes)

    # ------------------------------------------------------------- rounds --

    def _two_views_keyed(self, keys, gathered, k: int, n: int):
        """Augment gathered (K*n, ...) raw samples into stacked two-view
        batches (K, n, ...) with explicit per-sample keys (K*n, 2). The
        single source of truth for the view pipeline, shared by the host
        path (round_batch), the in-scan path (make_round_sampler), and the
        chunked path (make_streaming_sampler — which slices the SAME key
        array, so a chunk's views equal the materialized cohort's)."""
        out = {}
        if "images" in gathered:
            v1, v2 = jax.vmap(augment.two_views_image)(keys, gathered["images"])
            out["v1"] = v1.reshape(k, n, *v1.shape[1:])
            out["v2"] = v2.reshape(k, n, *v2.shape[1:])
        if "tokens" in gathered:
            v1, v2 = jax.vmap(
                lambda kk, tt: augment.two_views_tokens(kk, tt, self.vocab)
            )(keys, gathered["tokens"])
            out["v1"] = v1.reshape(k, n, *v1.shape[1:])
            out["v2"] = v2.reshape(k, n, *v2.shape[1:])
        return out

    def _two_views(self, k_aug, gathered, k: int, n: int):
        return self._two_views_keyed(jax.random.split(k_aug, k * n),
                                     gathered, k, n)

    def round_batch(self, key, clients_per_round: int):
        """Sample K clients, gather raw samples, build two augmented views.

        Returns (client_data pytree (K, n, ...), client_sizes (K,)).
        Gathers on the HOST — only the sampled cohort touches the device,
        so this works for corpora larger than device memory. The engine's
        in-scan twin is ``make_round_sampler`` (same math, tested equal)."""
        k_sel, k_aug = jax.random.split(key)
        sel = np.asarray(jax.random.choice(
            k_sel, self.num_clients, (clients_per_round,), replace=False))
        idx = self.client_index[sel]                          # (K, n)
        k, n = idx.shape
        gathered = {kk: jnp.asarray(v[idx.reshape(-1)])
                    for kk, v in self.data.items()}
        return self._two_views(k_aug, gathered, k, n), \
            jnp.asarray(self.client_sizes[sel], jnp.int32)

    def flat_round_batch(self, key, clients_per_round: int):
        """Same sampling, flattened to (K*n, ...) for the fused pod step."""
        batch, sizes = self.round_batch(key, clients_per_round)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
        return flat, sizes

    # ------------------------------------------------- in-scan sampling --

    def staged(self):
        """Device-resident (data, client_index, client_sizes), staged once
        per dataset and shared by every in-scan sampler. Each data leaf is
        stored as (N, features) rows (``_gather`` restores the per-sample
        shape): a TPU tiles an array's two minor dims to (8, 128), so
        32x32x3 images kept 4-D would pad their channel dim 3 -> 128."""
        if self._staged is None:
            self._staged = ({k: jnp.asarray(v.reshape(v.shape[0], -1))
                             for k, v in self.data.items()},
                            jnp.asarray(self.client_index),
                            jnp.asarray(self.client_sizes, jnp.int32))
        return self._staged

    def _gather(self, data, idx):
        """Samples ``idx`` of the staged data rows, in per-sample shape."""
        return {kk: v[idx].reshape(idx.shape + self.data[kk].shape[1:])
                for kk, v in data.items()}

    def make_round_sampler(self, clients_per_round: int):
        """A jax-traceable ``sampler(k_sel, k_aug) -> (batch, sizes)``.

        The whole dataset and client index are staged onto device once per
        dataset (cached, shared by all samplers); the sampler does cohort
        selection, gather, and the two-view augmentation with pure jax
        ops, so it can run INSIDE a scan body (repro.core.round_engine).
        It is a ``jax.tree_util.Partial`` whose leaves are the staged
        arrays: passed to a jitted function as an argument, the dataset is
        an input of the compiled program, not a constant folded into it (a
        50,000-image corpus would be ~600 MB of literals). Assumes the
        dataset fits in device memory — the paper's decentralized corpora
        are small; for a corpus larger than device memory, use the
        host-gathering ``round_batch``.
        """
        if clients_per_round in self._samplers:
            return self._samplers[clients_per_round]
        num_clients, n = self.num_clients, self.samples_per_client
        k_round = clients_per_round

        def sample(staged, k_sel, k_aug):
            data, cindex, csizes = staged
            sel = jax.random.choice(k_sel, num_clients, (k_round,),
                                    replace=False)
            idx = cindex[sel].reshape(-1)                    # (K*n,)
            gathered = self._gather(data, idx)
            out = self._two_views(k_aug, gathered, k_round, n)
            return out, csizes[sel]

        sampler = Partial(sample, self.staged())
        self._samplers[clients_per_round] = sampler
        return sampler

    def make_async_round_sampler(self, clients_per_round: int, latency=None):
        """``make_round_sampler``'s semi-synchronous twin: a jax-traceable
        ``sampler(k_sel, k_aug) -> (batch, sizes, delays)`` for the
        buffered engine (``EngineConfig.async_k``).

        ``delays`` (K,) int32 are per-contribution arrival delays in
        scheduler ticks, drawn from the ``latency`` model
        (:mod:`repro.data.latency`) on the TRUE sampled client ids — a
        heavy-tail model's stragglers therefore persist across rounds.
        The delay key is a ``fold_in`` salt off ``k_sel`` (no extra
        split), so cohort selection and augmentation are bit-identical to
        ``make_round_sampler`` for the same keys: zero-latency async runs
        see exactly the sync engine's batches.
        """
        from repro.data import latency as latency_lib
        model = latency_lib.resolve_latency(latency)
        num_clients, n = self.num_clients, self.samples_per_client
        k_round = clients_per_round

        def sample(staged, k_sel, k_aug):
            data, cindex, csizes = staged
            sel = jax.random.choice(k_sel, num_clients, (k_round,),
                                    replace=False)
            idx = cindex[sel].reshape(-1)                    # (K*n,)
            gathered = self._gather(data, idx)
            out = self._two_views(k_aug, gathered, k_round, n)
            sizes = csizes[sel]
            dk = jax.random.fold_in(k_sel, latency_lib._LATENCY_SALT)
            delays = latency_lib.sample_delays(model, dk,
                                               sel.astype(jnp.int32))
            return out, sizes, delays

        sampler = Partial(sample, self.staged())
        sampler.latency = model
        sampler.clients_per_round = k_round
        return sampler

    def make_streaming_sampler(self, clients_per_round: int,
                               cohort_chunk: int):
        """A chunkable sampler for the streaming engine path
        (``EngineConfig.cohort_chunk``): ``prepare(k_sel, k_aug)`` does the
        O(K)-scalar per-round work ONCE (cohort selection indices + the
        K*n per-sample augmentation keys — hoisted out of the chunk scan),
        and ``sample_chunk(state, c)`` gathers and augments ONLY chunk
        ``c``, so a round never materializes more than ``cohort_chunk``
        clients of batch data. Chunks concatenate to exactly the cohort
        ``make_round_sampler`` would emit for the same keys (same
        selection, same per-sample augmentation keys — tested), which is
        what makes streaming-vs-materialized equivalence checkable. As
        there, the staged arrays are leaves of the sampler (its callables
        are ``Partial``s over them), not constants of a compiled program.
        """
        from repro.hierarchy.streaming import StreamingSampler
        if cohort_chunk < 1 or clients_per_round % cohort_chunk:
            raise ValueError(
                f"clients_per_round={clients_per_round} does not divide "
                f"into chunks of {cohort_chunk}")
        num_clients, n = self.num_clients, self.samples_per_client
        k_round, chunk = clients_per_round, cohort_chunk

        def prepare(k_sel, k_aug):
            sel = jax.random.choice(k_sel, num_clients, (k_round,),
                                    replace=False)
            return sel, jax.random.split(k_aug, k_round * n)

        def sample_chunk(staged, state, c):
            data, cindex, csizes = staged
            sel, aug_keys = state
            sel_c = jax.lax.dynamic_slice(sel, (c * chunk,), (chunk,))
            idx = cindex[sel_c].reshape(-1)                  # (chunk*n,)
            gathered = self._gather(data, idx)
            keys = jax.lax.dynamic_slice(aug_keys, (c * chunk * n, 0),
                                         (chunk * n, 2))
            batch = self._two_views_keyed(keys, gathered, chunk, n)
            return batch, csizes[sel_c]

        def cohort_sizes(csizes, k_sel):
            # recomputes the cohort selection (same key -> same choice as
            # prepare), so variable-size clients report true sizes here too
            sel = jax.random.choice(k_sel, num_clients, (k_round,),
                                    replace=False)
            return csizes[sel]

        staged = self.staged()
        return StreamingSampler(k_round, chunk, Partial(prepare),
                                Partial(sample_chunk, staged),
                                Partial(cohort_sizes, staged[2]))
