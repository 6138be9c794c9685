"""Scan-compiled federated round engine.

The paper's runtime is hundreds-to-thousands of rounds over sampled cohorts
of tiny clients (Fig. 2). Driving :mod:`repro.core.fed_sim` one round at a
time from Python pays per-round dispatch, host-side cohort sampling, and a
re-trace whenever shapes wobble. The engine compiles the whole multi-round
loop into ONE XLA program:

  * ``lax.scan`` over rounds with a donated ``(params, opt_state, rng)``
    carry — no host round-trips, buffers reused in place;
  * in-scan client sampling via ``jax.random.fold_in(rng, round_index)``
    followed by on-device gather + augmentation (the sampler is a traced
    function, so cohort selection lives inside the scan body);
  * a pluggable round body: ``dcco`` | ``fedavg_cco`` | ``fedavg_contrastive``
    | ``fedavg_byol`` | ``centralized`` — all reuse the reference semantics in
    :mod:`repro.core.fed_sim`, so scan-of-N-rounds == N Python-driven rounds
    (tested in tests/test_round_engine.py); the stats bodies (dcco /
    fedavg_cco / centralized) are parametric in a
    :class:`repro.objectives.StatsObjective` (``EngineConfig.objective``:
    dcco / dvicreg / dwmse), whose stats dict rides every wire unchanged;
  * a sharded-cohort DCCO path: the (K, n, ...) client axis is laid across
    the mesh's data axis with ``shard_map``; the phase-1 stats aggregation
    and the phase-2 delta average become explicit psums — the wire protocol
    of Fig. 2 at device granularity (same pattern as core/dcco.py);
  * optional routing of the phase-1 aggregate statistics through the fused
    one-pass ``cco_stats_pallas`` kernel (exact by Eq. 3 — statistics are
    linear in samples, so the flattened-cohort stats equal the weighted
    average of per-client stats);
  * chunked scan segments: rounds run in segments of ``chunk_rounds`` so
    per-round metrics (loss, encoding-std collapse probe, wire bytes)
    stream back to the host between segments, where periodic checkpointing
    via ``repro.checkpoint`` hooks in;
  * a pluggable server-update strategy and client-drift correction
    (``EngineConfig.server_update`` / ``prox_mu`` / ``scaffold``,
    :mod:`repro.server`): the server step is any FedOpt-family
    ``ServerUpdate`` (plain delegate, FedAvgM, FedAdagrad/FedAdam/FedYogi),
    FedProx adds a proximal term to every local step, and SCAFFOLD control
    variates ride the scan carry as an extra pytree (server variate +
    per-cohort-slot client variates) whose uplink flows through the same
    channel as every other payload;
  * a pluggable communication channel (``EngineConfig.channel``,
    :mod:`repro.comm`): every client->server payload — phase-1 statistics
    and phase-2 deltas — is routed through the channel's encode/decode and
    participation-weighted aggregation INSIDE the scan body (dispatch is
    trace-time, so lossy wires cost no extra Python per round), with
    per-round bytes-on-the-wire in ``EngineMetrics.wire_bytes``;
  * hierarchical aggregation and streaming mega-cohorts
    (:mod:`repro.hierarchy`): a ``HierarchicalChannel`` fans the cohort in
    through edge aggregators (clients -> edges -> server, one comm channel
    per hop, both hops' bytes accounted — on the sharded path each device
    folds its local edges with the ``kernels/segment_sum.py`` one-pass
    kernel), and ``EngineConfig.cohort_chunk`` streams the cohort through
    the round in fixed-size chunks via an inner ``lax.scan`` whose carry
    holds only the running stat/delta sums — peak memory O(chunk) instead
    of O(cohort), which is what makes thousands-of-clients rounds fit.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import Partial

from repro.core import fed_sim, phases
from repro.kernels.ops import on_tpu
from repro.server import drift as drift_lib
from repro.server import update as server_update_lib

F32 = jnp.float32

ALGORITHMS = ("dcco", "fedavg_cco", "fedavg_contrastive", "fedavg_byol",
              "centralized")

# EngineConfig.compute_dtype spellings -> canonical jnp dtype. Only the
# encoder forward/backward runs in the compute dtype; every Eq.-3 statistic
# accumulation, loss, optimizer state, and master parameter stays f32
# (see cast_encoder_apply).
COMPUTE_DTYPES = {
    "float32": jnp.float32, "f32": jnp.float32, "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
}


def resolve_compute_dtype(compute_dtype):
    """Canonicalize an EngineConfig.compute_dtype spelling to a jnp dtype."""
    if compute_dtype in COMPUTE_DTYPES:
        return COMPUTE_DTYPES[compute_dtype]
    raise ValueError(f"unknown compute_dtype {compute_dtype!r}; expected one "
                     f"of {sorted(COMPUTE_DTYPES)}")


def cast_encoder_apply(encoder_apply: Callable, compute_dtype) -> Callable:
    """Mixed-precision wrapper: run the encoder forward/backward in
    ``compute_dtype`` while the Eq.-3 statistics stay f32.

    The paper's losses are computed from *sums of per-sample encoding
    statistics* (Eq. 3), and those sums divide near-cancelling quantities
    (correlation denominators), so the accumulation is the precision-
    critical path — the encoder forward is not. This wrapper casts float
    params and float batch leaves to ``compute_dtype`` at the encoder
    boundary and returns the (low-precision) encodings unchanged;
    ``cco.moment_stats`` — the ONE accumulator every stats objective
    shares — upcasts its inputs to f32 before any reduction, so every
    statistic, loss, delta, and optimizer buffer downstream of this
    wrapper is f32 regardless of the compute dtype (property-tested in
    tests/test_mixed_precision.py).

    The cast is linear, so ``grad`` through it yields f32 master-parameter
    gradients (the classic master-weights recipe); ``float32`` returns
    ``encoder_apply`` unchanged — statically zero-cost, bit-identical.
    Integer leaves (token ids, labels) pass through untouched.
    """
    dtype = resolve_compute_dtype(compute_dtype)
    if dtype == jnp.float32:
        return encoder_apply

    def cast_tree(tree):
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            tree)

    def apply(params, batch):
        return encoder_apply(cast_tree(params), cast_tree(batch))

    return apply

_CHANNEL_SALT = 0xC0                 # fold_in salt for the per-round comm key


class EngineConfig(NamedTuple):
    """Static configuration of the compiled round loop."""
    algorithm: str = "dcco"
    objective: Any = None           # StatsObjective instance or registered
                                    # name (repro.objectives) driving the
                                    # dcco/fedavg_cco/centralized bodies;
                                    # None = CCO with ``lam`` (pre-protocol
                                    # behavior, bit-identical)
    lam: float = 20.0
    temperature: float = 0.1
    client_lr: float = 1.0
    local_steps: int = 1
    chunk_rounds: int = 20          # rounds per jitted scan segment
    cohort_chunk: int = 0           # >0: stream the cohort through the
                                    # round in chunks of this many clients
                                    # (repro.hierarchy.streaming) — peak
                                    # memory O(cohort_chunk) instead of
                                    # O(cohort); requires a chunkable
                                    # sampler (make_streaming_sampler)
    scan_unroll: int = 0            # 0 = auto: 8 on CPU (XLA:CPU loses
                                    # inter-op parallelism inside while
                                    # bodies), 1 on accelerators
    donate: bool = True             # donate the (params, opt, rng) carry
    compute_dtype: str = "float32"  # encoder forward/backward dtype
                                    # ("float32" | "bfloat16"; aliases
                                    # f32/fp32/bf16). Statistics, losses,
                                    # deltas, optimizer state, and master
                                    # params stay f32 regardless — Eq.-3
                                    # accumulation is the precision-critical
                                    # path (see cast_encoder_apply)
    cohort_axis: Any = None         # mesh axis (or tuple of axes — the
                                    # multi-host data x client mesh) to
                                    # shard the K client axis over
    stats_kernel: str = "off"       # "off" | "pallas" | "interpret"
    channel: Any = None             # repro.comm Channel; None = ideal wire
    # --- server-optimization & client-drift subsystem (repro.server) ---
    server_update: Any = None       # repro.server ServerUpdate strategy;
                                    # None = wrap the engine's server_opt
                                    # argument as the bit-identical
                                    # fedavg_sgd delegate
    prox_mu: float = 0.0            # FedProx proximal coefficient (0 = off)
    scaffold: bool = False          # SCAFFOLD control variates: adds a
                                    # (c, c_slots) pytree to the scan carry
    # --- semi-synchronous buffered engine (repro.core.buffer) ---
    async_k: int = 0                # >0: FedBuff-style semi-synchronous
                                    # rounds — apply the server update when
                                    # this many client contributions have
                                    # ARRIVED (staleness-weighted buffer);
                                    # 0 = strictly synchronous rounds
    staleness_fn: Any = "unit"      # repro.core.buffer.STALENESS_FNS name
                                    # or callable tau -> weight
    latency: Any = None             # repro.data.latency model (None/"zero"/
                                    # "uniform"/"heavytail"/LatencyModel);
                                    # must match the async sampler's
    async_collapse: bool = True     # collapse the provably-synchronous
                                    # config (K = cohort, zero latency, unit
                                    # staleness) to the sync round body —
                                    # bit-identical baselines, same idiom as
                                    # HierarchicalChannel.collapse_ideal;
                                    # False forces the real buffered path
    # --- cluster-aware aggregation (repro.cluster) ---
    num_clusters: int = 0           # >1: cosine k-means on the per-client
                                    # Eq.-3 stats assigns each cohort
                                    # client a cluster inside the scan;
                                    # per-cluster correlation targets +
                                    # server-update slots (ClusterState
                                    # rides the carry). 0/1 = the global
                                    # path, bit-identical (structural
                                    # collapse, the async_collapse idiom)
    cluster_iters: int = 2          # Lloyd iterations per round (warm-
                                    # started from the carried centroids)
    cluster_fold: str = "jnp"       # per-cluster segment-sum fold impl
                                    # (hierarchy.FOLD_IMPLS; a
                                    # HierarchicalChannel's fold_impl wins)
    # --- periodic retrieval eval (repro.retrieval) ---
    retrieval_eval: Any = None      # traceable params -> {metric: scalar}
                                    # (repro.retrieval.make_retrieval_eval:
                                    # recall@k / MRR on a held-out corpus);
                                    # runs INSIDE the scan body so the
                                    # whole experiment stays one program.
                                    # A STATEFUL eval (make_refreshing_
                                    # retrieval_eval: .stateful, called as
                                    # (params, state) -> (metrics, state))
                                    # threads its index state through the
                                    # scan carry — drift-gated refresh
                                    # instead of a full re-encode per eval
    retrieval_every: int = 1        # evaluate on rounds where
                                    # round % retrieval_every == 0; skipped
                                    # rounds emit NaN (lax.cond, so the
                                    # encoder FLOPs are actually skipped)


class EngineCarry(NamedTuple):
    params: Any
    opt_state: Any
    rng: jnp.ndarray
    drift: Any = ()                 # drift-correction state (ScaffoldState
                                    # when EngineConfig.scaffold, else empty)
    buffer: Any = ()                # semi-synchronous buffer + in-flight
                                    # ring (buffer_lib.AsyncState when the
                                    # real buffered path runs, else empty)
    reval: Any = ()                 # stateful retrieval-eval state (the
                                    # refreshing eval's encoded corpus,
                                    # else empty) — threaded through the
                                    # scan so each periodic eval refreshes
                                    # rather than rebuilds the index
    cluster: Any = ()               # cluster-aware aggregation state
                                    # (repro.cluster.ClusterState when
                                    # EngineConfig.num_clusters > 1:
                                    # per-cluster params/opt slots +
                                    # warm-start centroids, else empty)


class EngineMetrics(NamedTuple):
    """Stacked per-round metrics, leading axis = rounds (= scheduler ticks
    on the buffered engine)."""
    loss: jnp.ndarray
    encoding_std: jnp.ndarray
    wire_bytes: jnp.ndarray = 0.0   # uplink bytes/round (0: ideal wire)
    applied: jnp.ndarray = 1.0      # server updates applied this tick
                                    # (sync rounds apply every round; the
                                    # buffered engine applies on K-triggers)
    staleness: jnp.ndarray = 0.0    # mean staleness (ticks) of the applied
                                    # aggregate, 0 when no update applied
    retrieval: Any = ()             # {"recall_at_k": (rounds,), "mrr":
                                    # (rounds,)} when EngineConfig.
                                    # retrieval_eval is set (NaN on rounds
                                    # the periodic eval skipped), else {}


# ---------------------------------------------------------------------------
# phase-1 aggregate statistics through the fused Pallas kernel
# ---------------------------------------------------------------------------

def make_kernel_agg_stats(interpret: bool = False,
                          second_moments: bool = False) -> Callable:
    """Aggregate cohort stats in one pass of the fused cco_stats kernel.

    ``second_moments`` selects the kernel's moment set (the objective's
    ``second_moments`` flag): "full" additionally emits the within-view
    moments VICReg-family objectives need, still in one pass.

    Rows are pre-masked (zeroed) and the normalizer is the true valid-sample
    count, which is exact for binary masks: (m*f)^2 = m*f^2 and
    (m*f)(m*g) = m*f*g.
    """
    from repro.kernels.cco_stats import cco_stats_pallas

    moments = "full" if second_moments else "cross"

    def agg_stats(zf, zg, mask):
        m = mask.astype(F32)[:, None]
        return cco_stats_pallas(zf.astype(F32) * m, zg.astype(F32) * m,
                                jnp.sum(mask.astype(F32)),
                                interpret=interpret, moments=moments)

    return agg_stats


def _resolve_agg_stats_fn(cfg: EngineConfig, objective) -> Optional[Callable]:
    if cfg.stats_kernel == "off":
        return None
    second = objective.second_moments
    if cfg.stats_kernel == "pallas":
        # compiled on a TPU, the (slow but exact) interpreter elsewhere
        return make_kernel_agg_stats(interpret=not on_tpu(),
                                     second_moments=second)
    if cfg.stats_kernel == "interpret":
        return make_kernel_agg_stats(interpret=True, second_moments=second)
    raise ValueError(f"unknown stats_kernel {cfg.stats_kernel!r}")


# ---------------------------------------------------------------------------
# sharded-cohort stats round (client axis on the mesh's data axis — or, on
# a multi-host mesh, on a (data, client) tuple of axes)
# ---------------------------------------------------------------------------

def _axis_names(axis):
    """Normalize a shard_map axis argument to a tuple of mesh-axis names."""
    return tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)


def _axis_pspec(axis):
    """PartitionSpec sharding dim 0 over one axis or a tuple of axes."""
    names = _axis_names(axis)
    return P(names[0] if len(names) == 1 else names)


def _axis_size(mesh, axis) -> int:
    size = 1
    for name in _axis_names(axis):
        size *= mesh.shape[name]
    return size


def _linear_axis_index(mesh, axis):
    """The shard's linear index over one axis or a row-major tuple of axes
    (``jax.lax.axis_index`` takes a single name on the supported jax
    range, so the multi-axis index is composed explicitly)."""
    names = _axis_names(axis)
    idx = jax.lax.axis_index(names[0])
    for name in names[1:]:
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    return idx


def stats_round_sharded(encoder_apply: Callable, params, opt_state,
                        server_opt, client_data, client_sizes, mesh, *,
                        objective,
                        client_lr: float = 1.0, local_steps: int = 1,
                        axis="data", channel=None, channel_key=None,
                        prox_mu: float = 0.0, scaffold_state=None):
    """One two-phase stats round (any StatsObjective) with the (K, n, ...)
    client axis sharded over ``axis``. ``dcco_round_sharded`` is the
    CCO-bound back-compat alias.

    Each shard hosts K/ndev clients; phase-1 aggregation and the phase-2
    delta average are explicit psums over ``axis`` — exactly the wire
    collectives of Fig. 2, reusing the psum pattern of core/dcco.py. The
    psum aggregation is exact for any registered objective because the
    protocol requires stats linear in samples (Eq. 3). Output equals the
    single-device ``fed_sim.stats_round`` (weights N_k/N are normalized
    by the psummed global sample count).

    With a ``channel`` (repro.comm) the collectives model a real wire:
    participation and the mask-renormalized weights come from
    ``channel.begin_round`` on the full cohort (sharded alongside sizes, so
    no psum renormalization is needed — the weights already sum to 1
    globally); each shard runs the per-client encode/decode locally with a
    shard-folded key; server-side post-processing (DP noise) uses the
    replicated round key, so every shard adds the *same* noise and the
    aggregate stays replicated.

    Drift correction mirrors ``fed_sim.dcco_round``: ``prox_mu`` is
    client-local (no collective); SCAFFOLD slot variates shard with the
    client axis (each device refreshes its own slots) while the server
    variate stays replicated — the variate-delta average is one more psum,
    channel-routed under the ``"variate"`` phase. With a ``scaffold_state``
    the round returns ``(params, opt_state, new_state, metrics)``.
    """
    server_update = server_update_lib.as_server_update(server_opt)
    if scaffold_state is not None and channel is not None:
        fed_sim.check_variate_noise(channel)
    n_pad = jax.tree.leaves(client_data)[0].shape[1]
    # a tuple of axes (the multi-host data x client mesh) shards the K
    # client axis over their product; psum over the tuple is the combined
    # in-host + cross-host wire aggregation (exact by Eq.-3 linearity —
    # any summation tree)
    axis = axis if isinstance(axis, str) else tuple(axis)
    p_axis = _axis_pspec(axis)
    nshards = _axis_size(mesh, axis)
    if channel is not None:
        if channel_key is None:
            raise ValueError("channel requires channel_key")
        ctx = channel.begin_round(channel_key, client_sizes)
    else:
        ctx = None

    def local_body(p, batch_l, sizes_l, *extra):
        extra = list(extra)
        masks = fed_sim._client_masks(sizes_l, n_pad)
        if channel is None:
            n_tot = jax.lax.psum(jnp.sum(sizes_l.astype(F32)), axis)
            w_l = sizes_l.astype(F32) / n_tot
            ctx_l, ckey = None, None
        else:
            from repro.comm.channel import ChannelContext
            # local view of the round context: payload randomness differs
            # per shard (fold in the shard index), server-side randomness
            # (post_aggregate) uses the replicated round key
            w_l, mask_l, ckey, num_part = extra[:4]
            del extra[:4]
            shard_key = jax.random.fold_in(ckey,
                                           _linear_axis_index(mesh, axis))
            ctx_l = ChannelContext(shard_key, mask_l, w_l, num_part)
        if scaffold_state is not None:
            # replicated server variate + this shard's slice of the slots
            state_l = drift_lib.ScaffoldState(*extra)

        def client_stats(batch, mask):
            zf, zg = encoder_apply(p, batch)
            return objective.stats_masked(zf, zg, mask)

        with jax.named_scope(phases.PHASE1):
            st_k = jax.vmap(client_stats)(batch_l, masks)
            if ctx_l is None:
                agg = {k: jax.lax.psum(jnp.tensordot(w_l, v, axes=1), axis)
                       for k, v in st_k.items()}
            else:
                # channel.local_fold is this shard's partial aggregate of
                # the decoded payloads (the base fold is the same tensordot
                # as above; a hierarchical channel folds its shard-local
                # edges here, kernels/segment_sum.py); the psum is the
                # server hop
                dec = channel.encode_decode(ctx_l, st_k, "stats")
                part = channel.local_fold(ctx_l, dec, "stats",
                                          num_shards=nshards)
                agg = {k: jax.lax.psum(v, axis) for k, v in part.items()}
                agg = channel.post_aggregate(
                    ctx_l._replace(key=ckey), agg, "stats")

        def client_update(batch, mask, corr=None):
            def loss_fn(pp):
                zf, zg = encoder_apply(pp, batch)
                local = objective.stats_masked(zf, zg, mask)
                return objective.loss_from_stats(objective.combine(local, agg))

            return fed_sim.client_local_steps(loss_fn, p, client_lr,
                                              local_steps, prox_mu=prox_mu,
                                              correction=corr)

        with jax.named_scope(phases.PHASE2):
            if scaffold_state is None:
                deltas, losses_k = jax.vmap(client_update)(batch_l, masks)
            else:
                deltas, losses_k = jax.vmap(client_update)(
                    batch_l, masks, drift_lib.scaffold_corrections(state_l))
        raw_deltas = deltas
        with jax.named_scope(phases.DELTA_AVERAGE):
            if ctx_l is None:
                avg_delta = jax.tree.map(
                    lambda d: jax.lax.psum(jnp.tensordot(w_l, d, axes=1),
                                           axis),
                    deltas)
            else:
                dec_d = channel.encode_decode(ctx_l, deltas, "update")
                part_d = channel.local_fold(ctx_l, dec_d, "update",
                                            num_shards=nshards)
                avg_delta = jax.tree.map(lambda d: jax.lax.psum(d, axis),
                                         part_d)
                avg_delta = channel.post_aggregate(
                    ctx_l._replace(key=ckey), avg_delta, "update")
        loss = jax.lax.psum(jnp.sum(w_l * losses_k), axis)
        outs = (avg_delta, loss[None], agg)
        if scaffold_state is not None:
            # option-II refresh on this shard's slots from its raw deltas;
            # the variate-delta average is one more channel-routed psum
            ck_new = drift_lib.scaffold_new_slot_variates(
                state_l, raw_deltas, client_lr, local_steps)
            dc = jax.tree.map(lambda new, old: new - old,
                              ck_new, state_l.c_slots)
            if ctx_l is None:
                agg_dc = jax.tree.map(
                    lambda d: jax.lax.psum(jnp.tensordot(w_l, d, axes=1),
                                           axis), dc)
            else:
                dec_c = channel.encode_decode(ctx_l, dc, "variate")
                part_c = channel.local_fold(ctx_l, dec_c, "variate",
                                            num_shards=nshards)
                agg_dc = jax.tree.map(lambda d: jax.lax.psum(d, axis),
                                      part_c)
                agg_dc = channel.post_aggregate(
                    ctx_l._replace(key=ckey), agg_dc, "variate")
            # ck_new leaves the shard unmasked; the dropped-slot blend and
            # the server-variate fold happen once, outside the shard_map,
            # via drift_lib.scaffold_apply_round on the gathered outputs
            outs = outs + (ck_new, agg_dc)
        return outs

    extra_args, extra_specs = (), ()
    out_specs = (P(), P(), P())
    if channel is not None:
        # weights/mask shard with the client axis; the round key and the
        # participant count are replicated
        extra_args += (ctx.weights, ctx.mask, ctx.key, ctx.num_participants)
        extra_specs += (p_axis, p_axis, P(), P())
    if scaffold_state is not None:
        extra_args += (scaffold_state.c, scaffold_state.c_slots)
        extra_specs += (P(), p_axis)
        out_specs += (p_axis, P())        # slot variates sharded, agg_dc
                                          # replicated like any aggregate
    sharded = jax.shard_map(
        local_body, mesh=mesh,
        in_specs=(P(), p_axis, p_axis) + extra_specs,
        out_specs=out_specs, check_vma=False)
    outs = sharded(params, client_data, client_sizes, *extra_args)
    avg_delta, loss, agg = outs[:3]

    with jax.named_scope(phases.SERVER_UPDATE):
        params, opt_state = server_update.step(params, opt_state, avg_delta)
    enc_std = objective.encoding_std(agg)
    wire = 0.0
    if channel is not None:
        wire = channel.round_bytes(ctx, agg) + \
            channel.round_bytes(ctx, avg_delta)
    if scaffold_state is not None:
        ck_new, agg_dc = outs[3:]
        if channel is not None:
            wire = wire + channel.round_bytes(ctx, agg_dc)
        new_state = drift_lib.scaffold_apply_round(
            scaffold_state, ck_new, agg_dc,
            None if ctx is None else ctx.mask)
        return params, opt_state, new_state, fed_sim.RoundMetrics(
            loss.reshape(()), enc_std, jnp.asarray(wire, F32))
    return params, opt_state, fed_sim.RoundMetrics(loss.reshape(()), enc_std,
                                                   jnp.asarray(wire, F32))


def dcco_round_sharded(encoder_apply: Callable, params, opt_state, server_opt,
                       client_data, client_sizes, mesh, *, lam: float = 20.0,
                       objective=None, **round_kw):
    """Back-compat alias: sharded DCCO == ``stats_round_sharded`` with the
    CCO objective (``lam``); ``objective=`` selects another registered
    stats objective (then ``lam`` is ignored)."""
    return stats_round_sharded(
        encoder_apply, params, opt_state, server_opt, client_data,
        client_sizes, mesh,
        objective=fed_sim.resolve_objective(objective, lam), **round_kw)


# ---------------------------------------------------------------------------
# round bodies
# ---------------------------------------------------------------------------

def make_round_body(encoder_apply: Callable, server_opt, cfg: EngineConfig,
                    mesh=None) -> Callable:
    """Build round_fn(params, opt_state, drift, batch, sizes, key) for
    cfg.algorithm, returning (params, opt_state, drift, metrics). ``key``
    is the per-round channel key (ignored by bodies without a communication
    channel); ``drift`` is the drift-correction carry (a ScaffoldState when
    cfg.scaffold, otherwise passed through untouched)."""
    if cfg.algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {cfg.algorithm!r}; "
                         f"expected one of {ALGORITHMS}")
    if cfg.cohort_axis is not None and cfg.algorithm != "dcco":
        raise NotImplementedError(
            "sharded cohorts are implemented for the dcco body only")
    # the stats objective driving the dcco / fedavg_cco / centralized
    # bodies; None -> CCO with cfg.lam (bit-identical to the pre-protocol
    # engine). Resolution happens once, at build time — as does the
    # mixed-precision encoder wrap (float32 is the identity).
    encoder_apply = cast_encoder_apply(encoder_apply, cfg.compute_dtype)
    objective = fed_sim.resolve_objective(cfg.objective, cfg.lam)
    if cfg.objective is not None and cfg.algorithm in (
            "fedavg_contrastive", "fedavg_byol"):
        raise ValueError(
            f"algorithm {cfg.algorithm!r} trains a non-stats loss; "
            f"objective={objective!r} would be silently ignored")
    if cfg.algorithm == "centralized" and (cfg.scaffold or cfg.prox_mu):
        raise ValueError(
            "the centralized body has no local client training, so "
            "drift correction (scaffold / prox_mu) does not apply")
    server_update = server_update_lib.as_server_update(
        cfg.server_update if cfg.server_update is not None else server_opt)
    channel = cfg.channel
    if channel is not None:
        if cfg.scaffold:
            # build-time twin of the trace-time check in the round bodies
            fed_sim.check_variate_noise(channel)
        if cfg.algorithm == "centralized":
            raise ValueError(
                "the centralized body has no client->server wire; "
                "channel is not applicable")
        if cfg.stats_kernel != "off" and not channel.supports_flat_stats:
            raise ValueError(
                f"stats_kernel={cfg.stats_kernel!r} aggregates phase-1 "
                f"stats from the flattened cohort, which is incompatible "
                f"with {channel!r} (needs per-client payloads)")
        noise_phases = getattr(channel, "noise_phases", None)
        if (noise_phases is not None
                and cfg.algorithm.startswith("fedavg_")
                and "update" not in noise_phases):
            # fedavg has no stats uplink: a stats-only DP channel would add
            # zero noise while the accountant still reports a finite epsilon
            raise ValueError(
                f"{channel!r} noises only {noise_phases}, but "
                f"{cfg.algorithm!r} ships client updates only — construct "
                f"it with noise_phases=('update',) to noise the aggregate "
                f"it actually releases")

    def _with_drift(inner):
        """Adapt a fed_sim-style round call to the uniform
        (params, opt_state, drift, batch, sizes, key) body signature:
        with cfg.scaffold the inner round already returns the 4-tuple;
        otherwise the drift carry passes through untouched."""
        def round_fn(params, opt_state, drift, batch, sizes, key):
            if cfg.scaffold:
                return inner(params, opt_state, batch, sizes, key,
                             scaffold_state=drift)
            p, o, m = inner(params, opt_state, batch, sizes, key)
            return p, o, drift, m
        return round_fn

    if cfg.algorithm == "dcco":
        if cfg.cohort_axis is not None:
            if mesh is None:
                raise ValueError("cohort_axis requires a mesh")

            def inner(params, opt_state, batch, sizes, key, **drift_kw):
                return stats_round_sharded(
                    encoder_apply, params, opt_state, server_update, batch,
                    sizes, mesh, objective=objective, client_lr=cfg.client_lr,
                    local_steps=cfg.local_steps, axis=cfg.cohort_axis,
                    channel=channel, channel_key=key, prox_mu=cfg.prox_mu,
                    **drift_kw)
        else:
            agg_stats_fn = _resolve_agg_stats_fn(cfg, objective)

            def inner(params, opt_state, batch, sizes, key, **drift_kw):
                return fed_sim.stats_round(
                    encoder_apply, params, opt_state, server_update, batch,
                    sizes, objective=objective, client_lr=cfg.client_lr,
                    local_steps=cfg.local_steps, agg_stats_fn=agg_stats_fn,
                    channel=channel, channel_key=key, prox_mu=cfg.prox_mu,
                    **drift_kw)
        round_fn = _with_drift(inner)
    elif cfg.algorithm.startswith("fedavg_"):
        kind = {"fedavg_cco": "stats", "fedavg_contrastive": "contrastive",
                "fedavg_byol": "byol"}[cfg.algorithm]

        def inner(params, opt_state, batch, sizes, key, **drift_kw):
            return fed_sim.fedavg_round(
                encoder_apply, params, opt_state, server_update, batch, sizes,
                loss_kind=kind, objective=objective,
                temperature=cfg.temperature,
                client_lr=cfg.client_lr, local_steps=cfg.local_steps,
                channel=channel, channel_key=key, prox_mu=cfg.prox_mu,
                **drift_kw)
        round_fn = _with_drift(inner)
    else:  # centralized: union of the cohort, one large-batch stats step
        def round_fn(params, opt_state, drift, batch, sizes, key):
            n_pad = jax.tree.leaves(batch)[0].shape[1]
            union = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
            mask = fed_sim._client_masks(sizes, n_pad).reshape(-1)
            p, o, m = fed_sim.centralized_step(
                encoder_apply, params, opt_state, server_update, union,
                mask=mask, objective=objective)
            return p, o, drift, m

    return round_fn


# ---------------------------------------------------------------------------
# streaming round body (repro.hierarchy.streaming)
# ---------------------------------------------------------------------------

def make_streaming_round_body(encoder_apply: Callable, server_opt,
                              cfg: EngineConfig, sampler) -> Callable:
    """Build the streaming round body: round_fn(params, opt_state, drift,
    k_sel, k_aug, key, sampler) -> (params, opt_state, drift, metrics).
    Unlike the materialized bodies it samples INSIDE the round, one cohort
    chunk at a time, so the engine never holds more than
    ``cfg.cohort_chunk`` clients of batch data — the `sampler` must be
    chunkable (``FederatedDataset.make_streaming_sampler`` /
    ``repro.hierarchy.StreamingSampler``). It is checked here and passed
    again per call, as the segment's argument."""
    from repro.hierarchy import streaming as streaming_lib

    if cfg.algorithm != "dcco":
        raise ValueError(
            f"cohort_chunk streams the two-phase stats round only "
            f"(algorithm 'dcco'), got {cfg.algorithm!r}")
    if cfg.cohort_axis is not None:
        raise ValueError(
            "cohort_chunk and cohort_axis are two layouts for the same "
            "client axis — stream it or shard it, not both")
    if cfg.scaffold:
        raise ValueError(
            "SCAFFOLD keeps per-cohort-slot variates resident, which is "
            "exactly the O(cohort) state cohort_chunk removes — disable "
            "scaffold for streaming rounds")
    if cfg.stats_kernel != "off":
        raise ValueError(
            "stats_kernel aggregates phase-1 stats from the flattened "
            "materialized cohort; with cohort_chunk the cohort never "
            "materializes — use the default per-chunk accumulation")
    if not hasattr(sampler, "sample_chunk"):
        raise ValueError(
            "cohort_chunk needs a chunkable sampler "
            "(FederatedDataset.make_streaming_sampler or a "
            "repro.hierarchy.StreamingSampler), got a plain round sampler")
    if sampler.cohort_chunk != cfg.cohort_chunk:
        raise ValueError(
            f"sampler chunks {sampler.cohort_chunk} clients but "
            f"EngineConfig.cohort_chunk={cfg.cohort_chunk}")
    num_chunks = sampler.clients_per_round // cfg.cohort_chunk
    encoder_apply = cast_encoder_apply(encoder_apply, cfg.compute_dtype)
    objective = fed_sim.resolve_objective(cfg.objective, cfg.lam)
    # resolution to a ServerUpdate happens once, inside the round (the
    # same single coercion point as the materialized bodies)
    server_opt = (cfg.server_update if cfg.server_update is not None
                  else server_opt)
    channel = cfg.channel

    def round_fn(params, opt_state, drift, k_sel, k_aug, key, sampler):
        with jax.named_scope(phases.SAMPLE):
            sizes = sampler.cohort_sizes(k_sel)
            # per-round O(K)-scalar sampling state, hoisted out of both
            # phase scans
            state = sampler.prepare(k_sel, k_aug)
        p, o, m = streaming_lib.streaming_stats_round(
            encoder_apply, params, opt_state, server_opt,
            lambda c: sampler.sample_chunk(state, c),
            num_chunks, sizes, objective=objective,
            client_lr=cfg.client_lr, local_steps=cfg.local_steps,
            channel=channel, channel_key=key, prox_mu=cfg.prox_mu)
        return p, o, drift, m

    return round_fn


# ---------------------------------------------------------------------------
# semi-synchronous buffered round body (repro.core.buffer)
# ---------------------------------------------------------------------------

def make_async_round_body(encoder_apply: Callable, server_opt,
                          cfg: EngineConfig, k_cohort: int) -> Callable:
    """Build the buffered round body: round_fn(params, opt_state, drift,
    astate, batch, sizes, delays, key) -> (params, opt_state, drift,
    astate, metrics).

    Each scheduler tick dispatches a full cohort through the ordinary
    two-phase stats round math (phase-1 stats, dispatch-cohort aggregate,
    phase-2 deltas — the same folds as ``fed_sim.stats_round``), but the
    server update is DEFERRED: per-client contributions are scattered into
    the in-flight ring at their arrival delay with a staleness weight
    ``s(delay)`` riding the weighted segment-sum fold, this tick's
    arrivals fold into the server buffer, and the update applies only when
    ``cfg.async_k`` contributions have accumulated (then the buffer
    resets). Exact by Eq.-3 linearity — the buffer merely re-associates
    the weighted sum; see :mod:`repro.core.buffer`.
    """
    from repro.core import buffer as buffer_lib
    from repro.core import cco

    if cfg.algorithm != "dcco":
        raise ValueError(
            f"async_k buffers the two-phase stats round only "
            f"(algorithm 'dcco'), got {cfg.algorithm!r}")
    if cfg.cohort_axis is not None:
        raise ValueError(
            "async_k and cohort_axis are not composed: the buffered "
            "scheduler folds per-client contributions on one host — "
            "shard the cohort or buffer it, not both")
    if cfg.stats_kernel != "off":
        raise ValueError(
            "stats_kernel aggregates phase-1 stats from the flattened "
            "cohort; the async buffer scatters per-client contributions "
            "by arrival delay — needs per-client payloads")
    encoder_apply = cast_encoder_apply(encoder_apply, cfg.compute_dtype)
    objective = fed_sim.resolve_objective(cfg.objective, cfg.lam)
    staleness_fn = buffer_lib.resolve_staleness(cfg.staleness_fn)
    server_update = server_update_lib.as_server_update(
        cfg.server_update if cfg.server_update is not None else server_opt)
    channel = cfg.channel
    if channel is not None:
        if getattr(channel, "noise_phases", None) is not None:
            raise ValueError(
                f"{channel!r} with async_k: DP noise calibration across "
                f"staleness-weighted multi-tick aggregates is undefined "
                f"(the per-contribution weights change the sensitivity) — "
                f"run DP on the synchronous engine")
        if hasattr(channel, "hop_bytes") and not channel.collapses:
            raise ValueError(
                f"{channel!r} with async_k: a lossy edge hop folds "
                f"per-EDGE aggregates, but the buffer scatters per-CLIENT "
                f"contributions — use a collapsing (ideal-hop) tree or a "
                f"flat channel")
    k_trigger = float(cfg.async_k)

    def round_fn(params, opt_state, drift, astate, batch, sizes, delays,
                 key):
        n_pad = jax.tree.leaves(batch)[0].shape[1]
        masks = fed_sim._client_masks(sizes, n_pad)
        if channel is None:
            ctx = None
            w = sizes.astype(F32) / jnp.sum(sizes.astype(F32))
            pmask = jnp.ones((k_cohort,), F32)
        else:
            ctx = channel.begin_round(key, sizes)
            w, pmask = ctx.weights, ctx.mask
        wire = 0.0

        # ---- phase 1 (dispatch-synchronous): cohort stats -> aggregate.
        # The dispatch cohort's OWN aggregate drives phase 2 — the
        # stop-grad combine needs the round's population estimate at
        # dispatch time, before any of these contributions arrive.
        def client_stats(b, m):
            zf, zg = encoder_apply(params, b)
            return objective.stats_masked(zf, zg, m)

        st_k = jax.vmap(client_stats)(batch, masks)
        if ctx is None:
            st_wire = st_k
            agg = cco.weighted_average_stats(st_k, sizes.astype(F32))
        else:
            # same math as channel.aggregate, with the decoded per-client
            # payloads kept — they are what the ring scatters
            st_wire = channel.encode_decode(ctx, st_k, "stats")
            agg = jax.tree.map(lambda v: jnp.tensordot(w, v, axes=1),
                               st_wire)
            agg = channel.post_aggregate(ctx, agg, "stats")
            wire = wire + channel.round_bytes(ctx, agg)

        # ---- phase 2: local steps against the dispatch aggregate
        def client_update(b, m, corr=None):
            def loss_fn(p):
                zf, zg = encoder_apply(p, b)
                local = objective.stats_masked(zf, zg, m)
                return objective.loss_from_stats(
                    objective.combine(local, agg))

            return fed_sim.client_local_steps(
                loss_fn, params, cfg.client_lr, cfg.local_steps,
                prox_mu=cfg.prox_mu, correction=corr)

        if cfg.scaffold:
            deltas, losses_k = jax.vmap(client_update)(
                batch, masks, drift_lib.scaffold_corrections(drift))
        else:
            deltas, losses_k = jax.vmap(client_update)(batch, masks)
        if ctx is None:
            d_wire = deltas
        else:
            d_wire = channel.encode_decode(ctx, deltas, "update")
            wire = wire + channel.round_bytes(
                ctx, jax.tree.map(lambda x: x[0], deltas))
        if cfg.scaffold:
            # variate refresh stays dispatch-synchronous (client-side
            # state, never buffered); its uplink rides this tick's wire
            drift, extra = fed_sim._scaffold_round_tail(
                drift, deltas, cfg.client_lr, cfg.local_steps, w, ctx,
                channel)
            wire = wire + extra

        # ---- staleness-weighted scatter into the in-flight ring
        s_w = staleness_fn(delays.astype(F32))
        w_eff = w * s_w * pmask
        pending = buffer_lib.dispatch_fold(
            astate.pending, st_wire, d_wire, losses_k, w_eff, pmask,
            delays)
        arrived, pending = buffer_lib.ring_pop(pending)
        buf = buffer_lib.buffer_add(astate.buffer, arrived)

        # ---- apply the server update once K contributions accumulated
        do_apply = buf.count >= k_trigger
        _, avg_delta, mean_tau = buffer_lib.buffer_aggregate(buf)
        p_new, o_new = server_update.step(params, opt_state, avg_delta)
        sel = lambda new, old: jax.tree.map(            # noqa: E731
            lambda a, b: jnp.where(do_apply, a, b), new, old)
        params2, opt2 = sel(p_new, params), sel(o_new, opt_state)
        buf = buffer_lib.buffer_reset_where(buf, do_apply)
        astate2 = buffer_lib.AsyncState(
            buf, pending,
            astate.applied_total + do_apply.astype(jnp.int32))

        metrics = EngineMetrics(
            loss=jnp.sum(w * losses_k),
            encoding_std=objective.encoding_std(agg),
            wire_bytes=jnp.asarray(wire, F32),
            applied=do_apply.astype(F32),
            staleness=jnp.where(do_apply, mean_tau, 0.0))
        return params2, opt2, drift, astate2, metrics

    return round_fn


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class RoundEngine:
    """jit + lax.scan federated training driver.

    ``sampler(k_sel, k_aug) -> (batch, sizes)`` must be jax-traceable: it
    runs INSIDE the scan body (see FederatedDataset.make_round_sampler).
    The sampler is an argument of each jitted segment: the dataset's
    samplers are pytrees whose leaves are the staged arrays, so the
    compiled program holds no dataset-sized constants; a plain function
    is wrapped as ``jax.tree_util.Partial(fn)``, with no leaves.
    A full experiment is ceil(R / chunk_rounds) XLA program invocations
    instead of R Python dispatches.
    """

    def __init__(self, encoder_apply: Callable, server_opt,
                 sampler: Callable, config: EngineConfig = EngineConfig(),
                 mesh=None):
        if config.chunk_rounds < 1:
            raise ValueError(
                f"chunk_rounds must be >= 1, got {config.chunk_rounds}")
        if config.retrieval_every < 1:
            raise ValueError(
                f"retrieval_every must be >= 1, got {config.retrieval_every}")
        if config.retrieval_eval is not None and \
                not callable(config.retrieval_eval):
            raise ValueError(
                "retrieval_eval must be a traceable params -> {metric: "
                "scalar} callable (repro.retrieval.make_retrieval_eval) or "
                "a stateful (params, state) -> (metrics, state) eval "
                "(repro.retrieval.make_refreshing_retrieval_eval)")
        self._retrieval_stateful = bool(
            getattr(config.retrieval_eval, "stateful", False))
        if self._retrieval_stateful and \
                not callable(getattr(config.retrieval_eval, "init_state",
                                     None)):
            raise ValueError(
                "a stateful retrieval_eval must expose init_state(params) "
                "seeding its index state "
                "(repro.retrieval.make_refreshing_retrieval_eval does)")
        self._retrieval_template = None  # eval_shape of retrieval_eval,
                                         # resolved lazily on first run()
        self.config = config
        self.sampler = (Partial(sampler) if callable(sampler) and
                        not isinstance(sampler, Partial) else sampler)
        if mesh is not None:
            # the staged dataset lives on every mesh device from the start:
            # left on the default device, each segment call would copy it
            # out to the mesh again
            self.sampler = jax.device_put(self.sampler,
                                          NamedSharding(mesh, P()))
        self.drift_state = None      # final drift carry of the last run()
        self.buffer_state = None     # final AsyncState of the last run()
        self.cluster_state = None    # final ClusterState of the last run()
        self._streaming = config.cohort_chunk > 0
        self._async = config.async_k > 0
        self._async_real = False     # True when the buffered path runs
        if config.num_clusters < 0:
            raise ValueError(
                f"num_clusters must be >= 0, got {config.num_clusters}")
        # num_clusters <= 1: ONE cluster is the global aggregate by
        # definition, so route to the global path — structurally
        # bit-identical (the async_collapse / collapse_ideal idiom)
        self._clustered = config.num_clusters > 1
        if self._clustered:
            if self._async:
                raise ValueError(
                    "num_clusters and async_k are not composed: the "
                    "buffered scheduler re-associates contributions "
                    "across ticks, but cluster targets and slots are "
                    "per-dispatch — cluster the synchronous engine")
            if self._streaming:
                raise ValueError(
                    "num_clusters assigns clusters from the materialized "
                    "cohort's per-client stats; cohort_chunk never "
                    "materializes the cohort — drop one")
            if config.cohort_axis is not None:
                raise ValueError(
                    "num_clusters and cohort_axis are not composed: the "
                    "k-means assignment and per-cluster slots fold on one "
                    "host — shard the cohort or cluster it, not both")
        if self._async:
            from repro.core import buffer as buffer_lib
            from repro.data import latency as latency_lib
            if self._streaming:
                raise ValueError(
                    "async_k and cohort_chunk are two schedulers for the "
                    "same round (buffered arrivals vs streamed chunks) "
                    "and are not composed — drop one")
            if not hasattr(sampler, "latency"):
                raise ValueError(
                    "async_k needs a latency-aware sampler emitting "
                    "(batch, sizes, delays) — use "
                    "FederatedDataset.make_async_round_sampler or "
                    "repro.data.latency.make_async_sampler, got a plain "
                    "round sampler")
            lat = latency_lib.resolve_latency(config.latency)
            if sampler.latency != lat:
                raise ValueError(
                    f"sampler draws delays from {sampler.latency} but "
                    f"EngineConfig.latency resolves to {lat} — the ring "
                    f"horizon and the delay stream must agree")
            k_cohort = sampler.clients_per_round
            if not 1 <= config.async_k <= k_cohort:
                raise ValueError(
                    f"async_k={config.async_k} must be in [1, "
                    f"clients_per_round={k_cohort}]: fewer than one "
                    f"contribution never triggers, more than one cohort "
                    f"can never accumulate before the first apply")
            # resolve once so an unknown name fails at build, not in trace
            buffer_lib.resolve_staleness(config.staleness_fn)
            self._async_collapsed = (
                config.async_collapse and lat.kind == "zero"
                and config.staleness_fn in (None, "unit")
                and config.async_k == k_cohort)
            if self._async_collapsed:
                # K = cohort, zero latency, unit staleness: every dispatch
                # arrives immediately and triggers exactly one apply — the
                # buffered round IS the synchronous round, so compute it
                # as one (bit-identical, the collapse_ideal idiom)
                self.round_fn = make_round_body(encoder_apply, server_opt,
                                                config, mesh)
            else:
                self.round_fn = make_async_round_body(
                    encoder_apply, server_opt, config, k_cohort)
                self._async_real = True
                self._async_horizon = lat.horizon
                self._objective = fed_sim.resolve_objective(
                    config.objective, config.lam)
                self._encoder_apply = encoder_apply
        elif self._streaming:
            self.round_fn = make_streaming_round_body(
                encoder_apply, server_opt, config, sampler)
        elif self._clustered:
            from repro.cluster import make_cluster_round_body
            self.round_fn = make_cluster_round_body(encoder_apply,
                                                    server_opt, config)
            # kept for sizing the fresh ClusterState (stats row width via
            # jax.eval_shape — no FLOPs), same idiom as the async buffer
            self._objective = fed_sim.resolve_objective(
                config.objective, config.lam)
            self._encoder_apply = encoder_apply
        else:
            self.round_fn = make_round_body(encoder_apply, server_opt,
                                            config, mesh)
        donate = (0,) if config.donate else ()
        self._segment = jax.jit(
            functools.partial(self._run_segment, config.chunk_rounds),
            donate_argnums=donate)
        self._tail_segments = {}   # tail length -> jitted segment
        self._donate = donate

    # -- one scan segment ---------------------------------------------------
    def _run_segment(self, num_rounds: int, carry: EngineCarry, start,
                     sampler):
        def body(c, r):
            rkey = jax.random.fold_in(c.rng, r)
            k_sel, k_aug = jax.random.split(rkey)
            # channel randomness comes from a fold_in (not a 3-way split)
            # so the selection/augmentation streams are unchanged vs the
            # channel-less engine — resume and regression baselines hold
            k_ch = jax.random.fold_in(rkey, _CHANNEL_SALT)
            buffer, cluster = c.buffer, c.cluster
            if self._async_real:
                with jax.named_scope(phases.SAMPLE):
                    batch, sizes, delays = sampler(k_sel, k_aug)
                params, opt_state, drift, buffer, m = self.round_fn(
                    c.params, c.opt_state, c.drift, c.buffer, batch, sizes,
                    delays, k_ch)
                applied, stale = m.applied, m.staleness
            elif self._streaming:
                # the streaming body samples inside the round, one cohort
                # chunk at a time — the full batch never materializes here
                params, opt_state, drift, m = self.round_fn(
                    c.params, c.opt_state, c.drift, k_sel, k_aug, k_ch,
                    sampler)
                applied, stale = jnp.ones((), F32), jnp.zeros((), F32)
            elif self._clustered:
                with jax.named_scope(phases.SAMPLE):
                    batch, sizes = sampler(k_sel, k_aug)
                params, opt_state, cluster, m = self.round_fn(
                    c.params, c.opt_state, c.cluster, batch, sizes, k_ch)
                drift = c.drift
                applied, stale = jnp.ones((), F32), jnp.zeros((), F32)
            else:
                with jax.named_scope(phases.SAMPLE):
                    if self._async:
                        # collapsed async config: same cohorts (delays are
                        # a fold_in side stream off k_sel), sync round body
                        batch, sizes, _delays = sampler(k_sel, k_aug)
                    else:
                        batch, sizes = sampler(k_sel, k_aug)
                params, opt_state, drift, m = self.round_fn(
                    c.params, c.opt_state, c.drift, batch, sizes, k_ch)
                applied, stale = jnp.ones((), F32), jnp.zeros((), F32)
            rmet, reval = self._retrieval_metrics(params, r, c.reval)
            return (EngineCarry(params, opt_state, c.rng, drift, buffer,
                                reval, cluster),
                    EngineMetrics(m.loss, m.encoding_std,
                                  jnp.asarray(m.wire_bytes, F32),
                                  applied, stale, rmet))

        unroll = self.config.scan_unroll or (
            8 if jax.default_backend() == "cpu" else 1)
        xs = start + jnp.arange(num_rounds)
        return jax.lax.scan(body, carry, xs,
                            unroll=min(unroll, num_rounds))

    def _retrieval_metrics(self, params, r, state):
        """The periodic in-scan retrieval eval on round ``r``'s params:
        (metrics, state) — the configured eval on rounds hitting the
        cadence, a NaN-filled template otherwise (lax.cond — the skipped
        branch costs nothing at runtime). A stateful eval's index state
        threads through (unchanged on skipped rounds); ((), state) when no
        retrieval eval is configured."""
        eval_fn = self.config.retrieval_eval
        if eval_fn is None:
            return (), state
        on_cadence = (r % self.config.retrieval_every) == 0

        def nan_template():
            return jax.tree.map(lambda s: jnp.full(s.shape, jnp.nan, F32),
                                self._retrieval_template)

        if self._retrieval_stateful:
            def run_eval(p, s):
                m, s2 = eval_fn(p, s)
                return jax.tree.map(lambda x: jnp.asarray(x, F32), m), s2

            def skip_eval(_p, s):
                return nan_template(), s

            return jax.lax.cond(on_cadence, run_eval, skip_eval,
                                params, state)

        def run_eval(p):
            return jax.tree.map(lambda x: jnp.asarray(x, F32), eval_fn(p))

        def skip_eval(_p):
            return nan_template()

        return jax.lax.cond(on_cadence, run_eval, skip_eval, params), state

    def _segment_fn(self, num_rounds: int):
        if num_rounds == self.config.chunk_rounds:
            return self._segment
        if num_rounds not in self._tail_segments:
            self._tail_segments[num_rounds] = jax.jit(
                functools.partial(self._run_segment, num_rounds),
                donate_argnums=self._donate)
        return self._tail_segments[num_rounds]

    def _init_async_state(self, params):
        """Zero AsyncState sized from the sampler/encoder shapes (no FLOPs:
        the encoding dim comes from ``jax.eval_shape``)."""
        from repro.core import buffer as buffer_lib
        k0 = jax.random.PRNGKey(0)
        batch_s, _, _ = jax.eval_shape(self.sampler, k0, k0)
        client0 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), batch_s)
        zf_s, _ = jax.eval_shape(self._encoder_apply, params, client0)
        return buffer_lib.init_state(
            self._objective.stat_spec(zf_s.shape[-1]), params,
            self._async_horizon)

    def _init_cluster_state(self, params, opt_state):
        """Fresh per-cluster slots; the centroid row width comes from the
        objective's stat_spec via ``jax.eval_shape`` (no FLOPs)."""
        from repro import cluster as cluster_lib
        k0 = jax.random.PRNGKey(0)
        batch_s, _ = jax.eval_shape(self.sampler, k0, k0)
        client0 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), batch_s)
        zf_s, _ = jax.eval_shape(self._encoder_apply, params, client0)
        dim = cluster_lib.stats_dim(
            self._objective.stat_spec(zf_s.shape[-1]))
        return cluster_lib.init_cluster_state(
            params, opt_state, self.config.num_clusters, dim)

    # -- full run -----------------------------------------------------------
    def run(self, params, opt_state, rng, rounds: int, *, start_round: int = 0,
            on_segment: Optional[Callable] = None, ckpt_dir: Optional[str] = None,
            ckpt_every: int = 0, ckpt_name: str = "engine",
            drift_state=None, buffer_state=None, cluster_state=None):
        """Run ``rounds`` rounds; returns (params, opt_state, EngineMetrics).

        Metrics stream back per segment; ``on_segment(round_end, carry,
        seg_metrics)`` fires after each segment; checkpoints are written at
        the first segment boundary at or past each ``ckpt_every`` multiple.

        With ``EngineConfig.retrieval_eval`` the returned (and per-segment)
        ``EngineMetrics.retrieval`` dict carries per-round recall@k / MRR
        (NaN on rounds the ``retrieval_every`` cadence skipped) — computed
        in-scan on the post-update params, alongside whatever probe the
        ``on_segment`` callback runs.

        With ``EngineConfig.scaffold``, the control variates ride the scan
        carry: pass ``drift_state=`` to resume from saved variates (zeros
        otherwise — the cohort size is inferred from the sampler via
        ``jax.eval_shape``), and read the final state from
        ``self.drift_state`` after the run (it is part of the returned
        carry, so it is safe to keep).

        On the real buffered path (``async_k`` without collapse) the
        staleness buffer and in-flight ring ride the carry the same way:
        pass ``buffer_state=`` to resume mid-flight contributions (zeros
        otherwise), read the final :class:`repro.core.buffer.AsyncState`
        from ``self.buffer_state``, and checkpoints gain a ``"buffer"``
        entry so save -> resume preserves in-flight work.

        With ``donate=True`` (default) the ``carry`` seen by ``on_segment``
        is donated to the NEXT segment: read it synchronously inside the
        callback (evaluate, log, ...) and ``jnp.copy`` anything you keep —
        retained references raise "Array has been deleted" later. The
        segment metrics are not donated and are safe to keep.
        """
        reval = ()
        if self.config.retrieval_eval is not None:
            if self._retrieval_stateful:
                # seed the refreshing eval's index state (the one full
                # chunked encode) from the run's initial params
                reval = self.config.retrieval_eval.init_state(params)
            if self._retrieval_template is None:
                # metric names/shapes of the periodic eval (no FLOPs) — the
                # NaN template the scan emits on skipped rounds
                if self._retrieval_stateful:
                    self._retrieval_template = jax.eval_shape(
                        lambda p, s: jax.tree.map(
                            lambda x: jnp.asarray(x, F32),
                            self.config.retrieval_eval(p, s)[0]),
                        params, reval)
                else:
                    self._retrieval_template = jax.eval_shape(
                        lambda p: jax.tree.map(
                            lambda x: jnp.asarray(x, F32),
                            self.config.retrieval_eval(p)),
                        params)
        drift = () if drift_state is None else drift_state
        if self.config.scaffold and drift_state is None:
            shapes = jax.eval_shape(
                self.sampler, jax.random.PRNGKey(0), jax.random.PRNGKey(0))
            drift = drift_lib.scaffold_init(params, shapes[1].shape[0])
        buffer = () if buffer_state is None else buffer_state
        if self._async_real and buffer_state is None:
            buffer = self._init_async_state(params)
        cluster = () if cluster_state is None else cluster_state
        if self._clustered and cluster_state is None:
            cluster = self._init_cluster_state(params, opt_state)
        carry = EngineCarry(params, opt_state, rng, drift, buffer, reval,
                            cluster)
        if self._donate:
            # segments donate their carry; copy once so the CALLER's buffers
            # survive the run (donation then recycles only engine-internal
            # buffers from segment to segment).
            with TraceAnnotation(phases.CARRY_COPY):
                carry = jax.tree.map(jnp.copy, carry)
        chunk = self.config.chunk_rounds
        cols = [[] for _ in EngineMetrics._fields]
        done, last_ckpt = 0, 0
        while done < rounds:
            seg = min(chunk, rounds - done)
            with TraceAnnotation(phases.DISPATCH):
                carry, m = self._segment_fn(seg)(
                    carry, jnp.asarray(start_round + done, jnp.int32),
                    self.sampler)
            done += seg
            with TraceAnnotation(phases.COLLECT):
                for col, v in zip(cols, m):
                    # the retrieval field is a dict of per-round arrays (or
                    # () when unused); everything else is a plain (seg,)
                    # array
                    col.append(v if isinstance(v, (dict, tuple))
                               else jnp.asarray(v, F32))
            round_end = start_round + done
            if on_segment is not None:
                on_segment(round_end, carry, m)
            if ckpt_dir and ckpt_every and (done - last_ckpt) >= ckpt_every:
                from repro.checkpoint import save_checkpoint
                path = os.path.join(ckpt_dir, f"{ckpt_name}.msgpack")
                blob = {"params": carry.params, "opt": carry.opt_state}
                if self.config.scaffold:
                    blob["drift"] = carry.drift
                if self._async_real:
                    blob["buffer"] = carry.buffer
                if self._clustered:
                    blob["cluster"] = carry.cluster
                with TraceAnnotation(phases.CHECKPOINT):
                    save_checkpoint(path, blob, round_end)
                last_ckpt = done
        self.drift_state = carry.drift if self.config.scaffold else None
        self.buffer_state = carry.buffer if self._async_real else None
        self.cluster_state = carry.cluster if self._clustered else None
        if self.config.channel is not None:
            # host-side bookkeeping (e.g. the DP epsilon accountant)
            self.config.channel.finalize_rounds(done)
        fields = []
        with TraceAnnotation(phases.COLLECT):
            for name, col in zip(EngineMetrics._fields, cols):
                if name == "retrieval":
                    if col and isinstance(col[0], dict):
                        fields.append({k: jnp.concatenate([c[k] for c in col])
                                       for k in col[0]})
                    else:
                        fields.append({})
                else:
                    fields.append(jnp.concatenate(col) if col
                                  else jnp.zeros((0,)))
        metrics = EngineMetrics(*fields)
        return carry.params, carry.opt_state, metrics
