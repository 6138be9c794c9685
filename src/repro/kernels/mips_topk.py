"""Fused maximum-inner-product-search + top-k Pallas TPU kernel.

The retrieval serving hot path (repro.retrieval): score Q normalized query
embeddings against an N-row corpus and keep each query's k best items. The
naive formulation materializes the full (Q, N) score matrix and then sorts
it — O(Q*N) HBM traffic and residency, which is exactly what kills serving
at corpus scale. This kernel fuses the two: (bq, d) query tiles and (bn, d)
corpus tiles are staged in VMEM, scores go through the MXU one (bq, bn)
tile at a time, and a running top-k (values + indices) lives in VMEM
scratch that persists across the innermost (corpus-block) grid dimension —
the same running-state idiom as flash_attention's (m, l) online softmax.
The (Q, N) matrix never exists anywhere in the memory hierarchy.

Selection: TPU Pallas has no in-kernel sort, so each merge runs k rounds of
(max, smallest-index-argmax, mask) over the (bq, k + bn) candidate row —
k is small (<= ~32) and the loop is unrolled at trace time. Ties break
toward the LOWEST corpus index, matching ``jax.lax.top_k``'s stable order,
so the kernel is bit-identical to ``ref.mips_topk_ref`` (scores are
computed by one full-depth f32 dot per element — d is never tiled, so no
re-association).

Three execution paths, one wrapper (``mips_topk``):
  * pallas   — the compiled TPU kernel;
  * interpret— the same kernel under the Pallas interpreter (CPU CI);
  * chunked  — pure-jnp lax.scan over corpus chunks carrying the running
               top-k (``mips_topk_chunked``): the CPU fallback with the
               same O(Q*chunk) peak memory and the same tie order.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels.ops import on_tpu

F32 = jnp.float32
I32 = jnp.int32
NEG_INF = -1e30         # sentinel score for padded corpus rows / used slots
BIG_IDX = 2 ** 30       # sentinel index (beats any real corpus index in min)


def _select_topk(cand_v, cand_i, k: int):
    """k rounds of (max, lowest-index pick, mask) over candidate rows.

    cand_v, cand_i: (m, c) f32 scores and i32 corpus indices. Returns
    ((m, k) values, (m, k) indices) sorted by descending value, ties by
    ascending index — exactly ``jax.lax.top_k``'s stable order. Sentinel
    (NEG_INF, BIG_IDX) pairs flow through harmlessly: they are only
    emitted when fewer than k real candidates exist, which the wrappers
    exclude (k <= N).
    """
    outs_v, outs_i = [], []
    for _ in range(k):
        m = jnp.max(cand_v, axis=1)
        at_max = cand_v == m[:, None]
        pick = jnp.min(jnp.where(at_max, cand_i, BIG_IDX), axis=1)
        taken = at_max & (cand_i == pick[:, None])
        cand_v = jnp.where(taken, NEG_INF, cand_v)
        outs_v.append(m)
        outs_i.append(pick)
    return jnp.stack(outs_v, axis=1), jnp.stack(outs_i, axis=1)


def _mips_kernel(q_ref, c_ref, v_ref, i_ref, v_scr, i_scr,
                 *, k: int, bq: int, bn: int, n_total: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        v_scr[...] = jnp.full_like(v_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, BIG_IDX)

    q = q_ref[...].astype(F32)                     # (bq, d)
    c = c_ref[...].astype(F32)                     # (bn, d)
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)       # (bq, bn)
    n_pos = ik * bn + jax.lax.broadcasted_iota(I32, (bq, bn), 1)
    valid = n_pos < n_total
    s = jnp.where(valid, s, NEG_INF)
    n_idx = jnp.where(valid, n_pos, BIG_IDX)

    cand_v = jnp.concatenate([v_scr[...], s], axis=1)         # (bq, k + bn)
    cand_i = jnp.concatenate([i_scr[...], n_idx], axis=1)
    new_v, new_i = _select_topk(cand_v, cand_i, k)
    v_scr[...] = new_v
    i_scr[...] = new_i

    @pl.when(ik == nk - 1)
    def _finish():
        v_ref[...] = v_scr[...]
        i_ref[...] = i_scr[...]


def _mips_kernel_offset(off_ref, q_ref, c_ref, v_ref, i_ref, v_scr, i_scr,
                        *, k: int, bq: int, bn: int, n_total: int,
                        n_local: int):
    """The shard-local variant: rows are a contiguous slice of a global
    corpus starting at ``off_ref[0, 0]`` (SMEM scalar), ``n_local`` is the
    UNPADDED local row count and ``n_total`` the GLOBAL corpus size. Two
    kinds of rows must mask to (NEG_INF, BIG_IDX): local block-padding
    rows (local position >= n_local — for a non-last shard their global
    position is a valid index belonging to the NEXT shard, so the global
    check alone cannot catch them) and rows past the global end (the
    ragged last shard). Emitted indices are global, so a cross-shard merge
    inherits the lowest-global-index tie order for free."""
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        v_scr[...] = jnp.full_like(v_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, BIG_IDX)

    q = q_ref[...].astype(F32)                     # (bq, d)
    c = c_ref[...].astype(F32)                     # (bn, d)
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)       # (bq, bn)
    local_pos = ik * bn + jax.lax.broadcasted_iota(I32, (bq, bn), 1)
    n_pos = off_ref[0, 0] + local_pos
    valid = (local_pos < n_local) & (n_pos < n_total)
    s = jnp.where(valid, s, NEG_INF)
    n_idx = jnp.where(valid, n_pos, BIG_IDX)

    cand_v = jnp.concatenate([v_scr[...], s], axis=1)         # (bq, k + bn)
    cand_i = jnp.concatenate([i_scr[...], n_idx], axis=1)
    new_v, new_i = _select_topk(cand_v, cand_i, k)
    v_scr[...] = new_v
    i_scr[...] = new_i

    @pl.when(ik == nk - 1)
    def _finish():
        v_ref[...] = v_scr[...]
        i_ref[...] = i_scr[...]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "n_total"))
def mips_topk_pallas(q, corpus, *, k: int, block_q: int = 128,
                     block_n: int = 512, interpret: bool = False,
                     index_offset=None, n_total: int | None = None):
    """q: (Q, d), corpus: (N, d) -> ((Q, k) f32 scores, (Q, k) i32 indices).

    Scores are plain inner products (callers normalize for cosine). Ragged
    Q/N pad up to block multiples; padded corpus rows are masked to
    (NEG_INF, BIG_IDX) positionally in-kernel, padded query rows are
    sliced off the output.

    ``index_offset`` (traced i32 scalar) switches to the shard-local
    variant: ``corpus`` is rows [offset, offset + N) of a global corpus of
    ``n_total`` rows (static), emitted indices are global, and both local
    block-padding rows and rows past the global end mask to sentinels.
    ``index_offset=None`` (default) compiles the exact pre-offset program.
    """
    qn, d = q.shape
    n, d2 = corpus.shape
    if d != d2:
        raise ValueError(f"query dim {d} != corpus dim {d2}")
    if not 1 <= k <= n:
        raise ValueError(f"k={k} must be in [1, corpus size {n}]")
    bq = min(block_q, qn)
    bn = min(block_n, n)
    q_pad = (-qn) % bq
    n_pad = (-n) % bn
    if q_pad:
        q = jnp.pad(q, ((0, q_pad), (0, 0)))
    if n_pad:
        corpus = jnp.pad(corpus, ((0, n_pad), (0, 0)))
    grid = ((qn + q_pad) // bq, (n + n_pad) // bn)

    nt = n if n_total is None else n_total
    if index_offset is None:
        # offset == 0, so global position == local position: folding the
        # local row count into n_total masks block padding and the global
        # end with the kernel's single check.
        kernel = functools.partial(_mips_kernel, k=k, bq=bq, bn=bn,
                                   n_total=min(n, nt))
        in_specs = [
            pl.BlockSpec((bq, d), lambda iq, ik: (iq, 0)),
            pl.BlockSpec((bn, d), lambda iq, ik: (ik, 0)),
        ]
        operands = (q, corpus)
    else:
        kernel = functools.partial(_mips_kernel_offset, k=k, bq=bq, bn=bn,
                                   n_total=nt, n_local=n)
        off = jnp.asarray(index_offset, I32).reshape(1, 1)   # SMEM scalar
        in_specs = [
            pl.BlockSpec((1, 1), lambda iq, ik: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, d), lambda iq, ik: (iq, 0)),
            pl.BlockSpec((bn, d), lambda iq, ik: (ik, 0)),
        ]
        operands = (off, q, corpus)
    vals, idxs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), lambda iq, ik: (iq, 0)),
            pl.BlockSpec((bq, k), lambda iq, ik: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn + q_pad, k), F32),
            jax.ShapeDtypeStruct((qn + q_pad, k), I32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), F32),     # running top-k values
            pltpu.VMEM((bq, k), I32),     # running top-k corpus indices
        ],
        interpret=interpret,
    )(*operands)
    return vals[:qn], idxs[:qn]


@functools.partial(jax.jit, static_argnames=("k", "chunk", "n_total"))
def mips_topk_chunked(q, corpus, *, k: int, chunk: int = 512,
                      index_offset=None, n_total: int | None = None):
    """Pure-jnp fallback: lax.scan over corpus chunks carrying the running
    top-k — same O(Q*chunk) peak memory and the same lowest-index tie
    order as the kernel (the running list keeps equal values in ascending
    corpus-index order, new chunks append strictly larger indices, and
    ``lax.top_k`` is stable — so the merge preserves the global order).

    ``index_offset``/``n_total`` mirror ``mips_topk_pallas``'s shard-local
    contract: indices come out global, and chunk-padding rows as well as
    rows past the global end mask to sentinels. ``index_offset`` may be a traced scalar (it is a
    ``lax.axis_index`` product under ``shard_map``); ``index_offset=None``
    (default) traces the exact pre-offset program.
    """
    qn, d = q.shape
    n, d2 = corpus.shape
    if d != d2:
        raise ValueError(f"query dim {d} != corpus dim {d2}")
    if not 1 <= k <= n:
        raise ValueError(f"k={k} must be in [1, corpus size {n}]")
    ch = min(chunk, n)
    n_pad = (-n) % ch
    if n_pad:
        corpus = jnp.pad(corpus, ((0, n_pad), (0, 0)))
    q = q.astype(F32)
    corpus = corpus.astype(F32)
    num_chunks = (n + n_pad) // ch
    nt = n if n_total is None else n_total

    def body(carry, c):
        vals, idxs = carry
        block = jax.lax.dynamic_slice_in_dim(corpus, c * ch, ch)
        s = jax.lax.dot_general(q, block, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)   # (Q, ch)
        local_pos = c * ch + jnp.arange(ch, dtype=I32)
        pos = local_pos
        if index_offset is not None:
            pos = pos + jnp.asarray(index_offset, I32)
        # mask chunk-padding rows by LOCAL position too: under an offset
        # their global position can be a valid next-shard index, so the
        # global check alone would emit (0.0, wrong-index) candidates
        valid = (local_pos < n) & (pos < nt)
        s = jnp.where(valid[None, :], s, NEG_INF)
        pos = jnp.where(valid, pos, BIG_IDX)
        cand_v = jnp.concatenate([vals, s], axis=1)
        cand_i = jnp.concatenate(
            [idxs, jnp.broadcast_to(pos[None, :], s.shape).astype(I32)],
            axis=1)
        new_v, at = jax.lax.top_k(cand_v, k)
        new_i = jnp.take_along_axis(cand_i, at, axis=1)
        return (new_v, new_i), None

    init = (jnp.full((qn, k), NEG_INF, F32),
            jnp.full((qn, k), BIG_IDX, I32))
    (vals, idxs), _ = jax.lax.scan(body, init,
                                   jnp.arange(num_chunks, dtype=I32))
    return vals, idxs


def mips_topk(q, corpus, k: int, *, backend: str = "auto",
              block_q: int = 128, block_n: int = 512, chunk: int = 512,
              interpret: bool = False, index_offset=None,
              n_total: int | None = None):
    """Top-k maximum-inner-product search, backend-dispatched.

    backend: "auto" (pallas on a TPU, chunked jnp elsewhere) |
    "pallas" | "interpret" (pallas under the interpreter) | "chunked".
    Returns ((Q, k) f32 scores, (Q, k) i32 corpus indices), descending
    score, ties by ascending index. Every path keeps peak memory at
    O(Q * block) — the (Q, N) score matrix is never materialized.

    ``index_offset``/``n_total`` select the shard-local variant on every
    backend (see mips_topk_pallas): ``corpus`` is a contiguous slice of a
    global ``n_total``-row corpus starting at ``index_offset``, indices
    come out global — the primitive repro.retrieval.sharded builds its
    bit-exact cross-shard merge on.
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "chunked"
    if backend in ("pallas", "interpret"):
        return mips_topk_pallas(q, corpus, k=k, block_q=block_q,
                                block_n=block_n,
                                interpret=interpret or backend == "interpret",
                                index_offset=index_offset, n_total=n_total)
    if backend == "chunked":
        return mips_topk_chunked(q, corpus, k=k, chunk=chunk,
                                 index_offset=index_offset, n_total=n_total)
    raise ValueError(f"unknown mips_topk backend {backend!r}; expected "
                     f"auto | pallas | interpret | chunked")
