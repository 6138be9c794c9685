"""Mesh-partitioned corpus search: shard the index, keep the bits.

``CorpusIndex`` holds the whole corpus on one device; this module
partitions the (N, d) embedding matrix over a mesh **"corpus"** axis so S
devices each hold a contiguous ~N/S-row slice and the serving hot path
scales with devices:

  1. every shard runs the SAME fused ``mips_topk`` kernel locally, told
     its place in the world via the kernel's ``index_offset``/``n_total``
     contract — local scores are the identical full-depth f32 dots (d is
     never tiled), emitted indices are GLOBAL, and invalid rows mask to
     (NEG_INF, BIG_IDX) in-kernel: the ragged last shard's rows past the
     global end AND each shard's internal block-padding rows (masked by
     local position — their global positions land in the next shard);
  2. the (Q, k) per-shard candidates are ``all_gather``-ed over the axis
     (k·Q small — the psum-style merge moves S·Q·k entries, never rows);
  3. one final selection over the S·k candidates per query
     (``_select_topk`` — the kernel's own value-desc / lowest-index-asc
     pick) emits the global top-k.

**Exactness argument** (tested bit-for-bit in tests/test_retrieval_scale
and the 2-process harness in tests/test_multihost.py): the global order
is (score desc, index asc) — ``lax.top_k``'s stable order over the full
corpus. Each shard's local top-k is the restriction of that order to its
rows, so every global top-k item survives into the gathered candidate
set; ``_select_topk`` then picks by the same (value, global-index) key,
so ties between duplicated rows in DIFFERENT shards still break toward
the lowest global index. Scores are bit-identical because each score is
one full-depth dot of the same two vectors — sharding re-tiles N, never
d, so no f32 sum is re-associated.

Two execution paths, same math:
  * ``mesh=None`` — a ``vmap`` over the stacked (S, shard_size, d)
    shards: single-device "simulated sharding", used by the tier-1
    exactness tests and the bench's per-shard timing;
  * ``mesh=Mesh(..., ("corpus",))`` — ``shard_map`` over the axis: each
    device keeps only its shard resident (S× index capacity), with
    ``lax.axis_index`` supplying the offset and a real all_gather the
    merge traffic. ``repro.sharding.make_corpus_mesh()`` builds the mesh
    over all devices, across hosts when jax.distributed is initialized.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.mips_topk import _select_topk, mips_topk
from repro.retrieval.index import CorpusIndex, encode_corpus_chunked, \
    refresh_embeddings

F32 = jnp.float32
I32 = jnp.int32


def stack_shards(embeddings, num_shards: int):
    """Contiguously partition (N, d) into (S, shard_size, d), zero-padding
    the last shard up to shard_size = ceil(N / S). Contiguity matters for
    exactness: shard s owns global rows [s * shard_size, ...), so its
    padding rows sit past the global end and mask in-kernel via
    ``n_total``."""
    n, d = embeddings.shape
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_shards > n:
        raise ValueError(f"num_shards={num_shards} exceeds corpus size {n}")
    shard_size = -(-n // num_shards)
    pad = num_shards * shard_size - n
    if pad:
        embeddings = jnp.concatenate(
            [embeddings, jnp.zeros((pad, d), embeddings.dtype)], axis=0)
    return embeddings.reshape(num_shards, shard_size, d)


def merge_topk(vals, idxs, k: int):
    """Merge (S, Q, k) per-shard candidates into the global (Q, k) top-k.

    ``_select_topk`` picks by (max value, lowest index) directly on the
    candidates' GLOBAL indices, so the result is invariant to shard order
    and bit-identical to single-device ``lax.top_k`` whenever the
    candidate set contains the true top-k (which per-shard top-k
    guarantees). Sentinel (NEG_INF, BIG_IDX) pads from short shards flow
    through harmlessly."""
    s, qn, kk = vals.shape
    cand_v = jnp.transpose(vals, (1, 0, 2)).reshape(qn, s * kk)
    cand_i = jnp.transpose(idxs, (1, 0, 2)).reshape(qn, s * kk)
    return _select_topk(cand_v.astype(F32), cand_i.astype(I32), k)


def sharded_mips_topk(q, shards, k: int, *, n_total: int, mesh=None,
                      axis: str = "corpus", backend: str = "auto", **kw):
    """Top-k MIPS over a stacked (S, shard_size, d) contiguous partition
    of an ``n_total``-row corpus; bit-identical to single-device
    ``mips_topk`` on the concatenated corpus (scores, indices, ties).

    ``mesh=None`` simulates the S shards with ``vmap`` on one device;
    with a mesh carrying ``axis``, the same per-shard program runs under
    ``shard_map`` with a real cross-device all_gather merge (queries
    replicated, shards partitioned, output replicated)."""
    s, shard_size, d = shards.shape
    if not 1 <= k <= min(shard_size, n_total):
        raise ValueError(
            f"k={k} must be in [1, min(shard_size={shard_size}, "
            f"n_total={n_total})] — every shard must be able to emit k "
            f"candidates; use fewer shards for larger k")

    def local(shard, off):
        return mips_topk(q, shard, k, backend=backend, index_offset=off,
                         n_total=n_total, **kw)

    if mesh is None:
        offsets = jnp.arange(s, dtype=I32) * shard_size
        vals, idxs = jax.vmap(local)(shards, offsets)       # (S, Q, k)
        return merge_topk(vals, idxs, k)

    def shard_body(q_rep, shards_loc):
        off = jax.lax.axis_index(axis).astype(I32) * shard_size
        v, i = mips_topk(q_rep, shards_loc[0], k, backend=backend,
                         index_offset=off, n_total=n_total, **kw)
        v = jax.lax.all_gather(v, axis)                     # (S, Q, k)
        i = jax.lax.all_gather(i, axis)
        return merge_topk(v, i, k)

    fn = jax.shard_map(shard_body, mesh=mesh, in_specs=(P(), P(axis)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(q, shards)


class ShardedCorpusIndex:
    """A :class:`CorpusIndex` partitioned over a mesh "corpus" axis.

    Drop-in for ``QueryServer``: same ``num_items``/``dim``/``search``
    surface, same results bit-for-bit. With a mesh, each shard is placed
    on its axis device (``NamedSharding(mesh, P("corpus"))``) — across
    processes each host materializes only its addressable shards."""

    def __init__(self, embeddings, num_shards: int, *, mesh=None,
                 axis: str = "corpus", normalized: bool = True):
        if embeddings.ndim != 2:
            raise ValueError(f"embeddings must be (N, d), "
                             f"got {embeddings.shape}")
        self.num_shards = int(num_shards)
        self.mesh = mesh
        self.axis = axis
        self.normalized = normalized
        self._n, self._d = embeddings.shape
        shards = stack_shards(embeddings, self.num_shards)
        if mesh is not None:
            if axis not in mesh.axis_names:
                raise ValueError(f"mesh {mesh.axis_names} has no "
                                 f"{axis!r} axis")
            ax_size = mesh.shape[axis]
            if ax_size != self.num_shards:
                raise ValueError(
                    f"num_shards={self.num_shards} must equal the mesh "
                    f"{axis!r} axis size {ax_size} (one shard per device)")
        self.shards = self._place(shards)

    def _place(self, shards):
        """Lay stacked (S, shard_size, d) shards out on the mesh axis —
        one shard per device; across processes each host contributes its
        addressable slice (jax.devices() enumerates in process order)."""
        if self.mesh is None:
            return shards
        if jax.process_count() > 1:
            from repro.sharding import host_local_to_global
            if self.num_shards % jax.process_count() != 0:
                raise ValueError(
                    f"num_shards={self.num_shards} must divide evenly "
                    f"across {jax.process_count()} processes — a ragged "
                    f"split would silently drop trailing shards from the "
                    f"host-local slice")
            per = self.num_shards // jax.process_count()
            lo = jax.process_index() * per
            return host_local_to_global(self.mesh, P(self.axis),
                                        shards[lo:lo + per])
        return jax.device_put(shards, NamedSharding(self.mesh, P(self.axis)))

    @property
    def num_items(self) -> int:
        return self._n

    @property
    def dim(self) -> int:
        return self._d

    @property
    def shard_size(self) -> int:
        return self.shards.shape[1]

    @classmethod
    def from_index(cls, index: CorpusIndex, num_shards: int, *, mesh=None,
                   axis: str = "corpus") -> "ShardedCorpusIndex":
        return cls(index.embeddings, num_shards, mesh=mesh, axis=axis,
                   normalized=index.normalized)

    @classmethod
    def build(cls, encode_fn: Callable, params, corpus, *, num_shards: int,
              mesh=None, axis: str = "corpus", chunk: int = 256,
              normalize: bool = True, dtype=jnp.float32):
        z = encode_corpus_chunked(encode_fn, params, corpus, chunk=chunk,
                                  normalize=normalize, dtype=dtype)
        return cls(z, num_shards, mesh=mesh, axis=axis, normalized=normalize)

    def refresh(self, encode_fn: Callable, params, corpus, *,
                threshold: float, block: int = 64,
                probes_per_block: int = 4) -> dict:
        """Drift-gated in-place shard update (see
        :func:`repro.retrieval.index.refresh_embeddings`): probe, re-encode
        only drifted blocks, re-stack, and re-place each shard on its mesh
        device. Requires the shards to be host-addressable — single
        process (any mesh) only; multi-process serving rebuilds via
        ``build``."""
        if jax.process_count() > 1:
            raise NotImplementedError(
                "ShardedCorpusIndex.refresh needs host-addressable shards; "
                "rebuild with ShardedCorpusIndex.build under multi-process "
                "serving")
        flat = jnp.asarray(self.shards).reshape(-1, self._d)[:self._n]
        new_emb, stats = refresh_embeddings(
            encode_fn, params, corpus, flat, threshold=threshold,
            block=block, probes_per_block=probes_per_block,
            normalize=self.normalized)
        self.shards = self._place(
            stack_shards(new_emb.astype(self.shards.dtype), self.num_shards))
        return {k: float(v) for k, v in stats.items()}

    def search(self, queries, k: int, *, backend: str = "auto", **kw):
        """Global top-k: queries (Q, d) -> ((Q, k) f32 scores, (Q, k) i32
        global item indices), bit-identical to the unsharded
        ``CorpusIndex.search``."""
        return sharded_mips_topk(queries.astype(F32), self.shards, k,
                                 n_total=self._n, mesh=self.mesh,
                                 axis=self.axis, backend=backend, **kw)
