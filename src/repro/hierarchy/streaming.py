"""Streaming mega-cohort execution — cohort size as a memory-free knob.

The materialized engine paths stack the whole cohort on one axis: a round
of K clients holds (K, n, ...) batches and (K, ...) per-client deltas
live at once, capping clients/round far below the cross-device
populations FedAvg targets (thousands of devices, tiny local datasets).

Because every payload is linear in samples (paper Eq. 3), the round does
not need the cohort in memory: this module runs the two-phase stats
protocol over fixed-size cohort *chunks* with an inner ``lax.scan`` whose
carry holds only the running stat-sums / delta-sums — peak memory is
O(cohort_chunk), independent of K, and the result equals the materialized
round up to float regrouping (tested). The streamed round IS the Fig.-2
protocol read literally: the server only ever touches aggregates.

  phase 1: scan chunks — encode each chunk's clients, fold their stats
           into the carry with the chunk's slice of the global Eq.-3
           weights (``Channel.chunk_fold``, so quantization / dropout /
           hierarchical edge trees compose) — then one ``post_aggregate``;
  phase 2: scan chunks again — each chunk's clients take their local
           steps against the stop-grad combine with the phase-1 aggregate,
           and only the weighted delta partial survives the chunk.

Phase 2 re-gathers and re-augments each chunk (the chunk sampler is
deterministic in (k_sel, k_aug, chunk)), which costs no extra encoder
FLOPs vs the materialized round — phase 1 is forward-only and phase 2
re-encodes under the gradient there too.

Note XLA:CPU serializes scan bodies, so on CPU the inner scan trades the
unrolled cohort's inter-op parallelism for bounded memory — the
``population_scale`` benchmark measures exactly that trade (round time
and compiled peak memory vs chunk size).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import fed_sim
from repro.server import update as server_update_lib

F32 = jnp.float32


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["prepare", "sample_chunk", "cohort_sizes"],
                   meta_fields=["clients_per_round", "cohort_chunk"])
@dataclasses.dataclass(frozen=True)
class StreamingSampler:
    """A chunkable cohort sampler for the streaming engine path.

    ``prepare(k_sel, k_aug)`` computes the per-round O(K)-scalar state
    once, OUTSIDE the chunk scans (selection indices, augmentation keys
    — cheap to hold, and hoisting it keeps the scan bodies free of
    repeated cohort-wide work); ``sample_chunk(state, c)`` returns chunk
    ``c`` of the round's cohort — ``(batch (chunk, n, ...), sizes
    (chunk,))`` — and must be deterministic in its arguments (phase 2
    replays it); ``cohort_sizes(k_sel)`` returns the full (K,) client
    sizes (channels need them for participation and Eq.-3 weights — the
    *batches* are what never materialize).
    The sampler is a pytree, so the engine can pass it to its jitted
    segment as an argument: the three callables are
    ``jax.tree_util.Partial``s, whose bound arrays are its leaves (a plain
    function is ``Partial(fn)``, with none).
    ``FederatedDataset.make_streaming_sampler`` builds one whose chunks
    concatenate to exactly ``make_round_sampler``'s cohort.
    """
    clients_per_round: int
    cohort_chunk: int
    prepare: Callable
    sample_chunk: Callable
    cohort_sizes: Callable

    @property
    def num_chunks(self) -> int:
        return self.clients_per_round // self.cohort_chunk


def streaming_stats_round(encoder_apply: Callable, params, opt_state,
                          server_opt, sample_chunk: Callable,
                          num_chunks: int, client_sizes, *, objective,
                          client_lr: float = 1.0, local_steps: int = 1,
                          channel=None, channel_key=None,
                          prox_mu: float = 0.0):
    """One two-phase stats round streamed over ``num_chunks`` cohort
    chunks. Semantically ``fed_sim.stats_round`` on the concatenated
    cohort (same objective/channel/drift contracts, minus SCAFFOLD — slot
    variates are cohort-resident state, which is exactly what streaming
    removes); returns (params, opt_state, RoundMetrics).

    ``sample_chunk(c) -> (batch, sizes)`` is the already-keyed chunk
    closure; ``client_sizes`` is the full (K,) cohort sizes array.
    """
    server_update = server_update_lib.as_server_update(server_opt)
    k = client_sizes.shape[0]
    if k % num_chunks:
        raise ValueError(f"cohort of {k} does not divide into "
                         f"{num_chunks} chunks")
    chunk = k // num_chunks
    if channel is not None:
        if channel_key is None:
            raise ValueError("channel requires channel_key")
        ctx = channel.begin_round(channel_key, client_sizes)
        w = ctx.weights
    else:
        ctx = None
        w = client_sizes.astype(F32) / jnp.sum(client_sizes.astype(F32))

    def w_slice(c):
        return jax.lax.dynamic_slice(w, (c * chunk,), (chunk,))

    def chunk_stats(c):
        batch, sizes_c = sample_chunk(c)
        n_pad = jax.tree.leaves(batch)[0].shape[1]
        masks = fed_sim._client_masks(sizes_c, n_pad)

        def client_stats(b, m):
            zf, zg = encoder_apply(params, b)
            return objective.stats_masked(zf, zg, m)

        st_k = jax.vmap(client_stats)(batch, masks)
        if ctx is None:
            return jax.tree.map(
                lambda v: jnp.tensordot(w_slice(c), v, axes=1), st_k)
        return channel.chunk_fold(ctx, st_k, "stats", c, w_slice(c))

    # ---- phase 1: stream the chunks, accumulate the stat partials.
    # Chunk 0 runs outside the scan and seeds the carry — no zero
    # templates to derive, and a 1-chunk cohort never builds a scan.
    acc0 = chunk_stats(0)
    if num_chunks > 1:
        agg_sum, _ = jax.lax.scan(
            lambda acc, c: (jax.tree.map(jnp.add, acc, chunk_stats(c)),
                            None),
            acc0, jnp.arange(1, num_chunks))
    else:
        agg_sum = acc0
    agg = agg_sum if ctx is None else channel.post_aggregate(ctx, agg_sum,
                                                             "stats")

    # ---- phase 2: stream again, clients step against the combine
    def chunk_update(c):
        batch, sizes_c = sample_chunk(c)
        n_pad = jax.tree.leaves(batch)[0].shape[1]
        masks = fed_sim._client_masks(sizes_c, n_pad)

        def client_update(b, m):
            def loss_fn(p):
                zf, zg = encoder_apply(p, b)
                local = objective.stats_masked(zf, zg, m)
                return objective.loss_from_stats(
                    objective.combine(local, agg))

            return fed_sim.client_local_steps(loss_fn, params, client_lr,
                                              local_steps, prox_mu=prox_mu)

        deltas, losses_k = jax.vmap(client_update)(batch, masks)
        wc = w_slice(c)
        if ctx is None:
            part = jax.tree.map(lambda d: jnp.tensordot(wc, d, axes=1),
                                deltas)
        else:
            part = channel.chunk_fold(ctx, deltas, "update", c, wc)
        return part, jnp.sum(wc * losses_k)

    d0, l0 = chunk_update(0)
    if num_chunks > 1:
        def p2_body(carry, c):
            part, lo = chunk_update(c)
            return (jax.tree.map(jnp.add, carry[0], part),
                    carry[1] + lo), None

        (delta_sum, loss), _ = jax.lax.scan(p2_body, (d0, l0),
                                            jnp.arange(1, num_chunks))
    else:
        delta_sum, loss = d0, l0
    avg_delta = delta_sum if ctx is None else channel.post_aggregate(
        ctx, delta_sum, "update")

    params, opt_state = server_update.step(params, opt_state, avg_delta)
    enc_std = objective.encoding_std(agg)
    wire = 0.0
    if ctx is not None:
        wire = channel.round_bytes(ctx, agg) + \
            channel.round_bytes(ctx, avg_delta)
    return params, opt_state, fed_sim.RoundMetrics(loss, enc_std,
                                                   jnp.asarray(wire, F32))
