"""Client-level federated simulator — the paper's training runtime.

This is the *protocol-faithful* implementation: explicit client sampling,
model broadcast, the two server<->client communication phases of DCCO
(Fig. 2), local training, and the FedOpt-style server update. The pod-scale
fused path (launch/steps.py) is the performance implementation; this module
is the reference semantics, and tests assert they agree (Appendix A).

Client data layout: a pytree whose leaves have leading dims (K, n, ...) —
K clients, n samples each (padded; ``mask`` (K, n) marks real samples, so
variable-size clients like DERM's 1-6 images/case are supported).

``encoder_apply(params, batch) -> (zf, zg)`` abstracts the dual encoding
model: batch is one client's (n, ...) slice holding both views.

The rounds here materialize the whole cohort on the leading K axis. Two
scale-out companions share their exact semantics: the sharded-cohort path
(:func:`repro.core.round_engine.stats_round_sharded`, K laid across the
device mesh) and the streaming path
(:func:`repro.hierarchy.streaming.streaming_stats_round`, K processed in
O(chunk)-memory chunks) — both exact by Eq. 3 because every payload is
linear in samples, and both reusing the comm ``channel`` contract
(a :class:`repro.hierarchy.HierarchicalChannel` additionally fans the
aggregation in through edge aggregators with one channel per hop).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import utils
from repro.core import cco, losses, phases
from repro.optim import optimizers as opt_lib
from repro.server import drift as drift_lib
from repro.server import update as server_update_lib

F32 = jnp.float32


def resolve_objective(objective, lam: float = 20.0):
    """Resolve an objective name/instance; ``None`` -> CCO with ``lam``.

    ``lam`` is CCO's hyperparameter, so it also applies when the CCO
    objective is requested *by name* — ``objective="dcco", lam=5.0`` must
    not silently train with the default lam. Other names/instances carry
    their own hyperparameters and ignore ``lam``.

    Imported lazily: ``repro.objectives`` builds on ``repro.core``, so a
    module-level import here would be circular.
    """
    from repro import objectives as objectives_lib
    if objective is None or objective == "dcco":
        return objectives_lib.CCOObjective(lam=lam)
    return objectives_lib.get_objective(objective)


class RoundMetrics(NamedTuple):
    loss: jnp.ndarray
    encoding_std: jnp.ndarray
    # uplink bytes this round (0 when no comm channel is modeled)
    wire_bytes: Any = 0.0


def sample_clients(key, num_clients: int, clients_per_round: int):
    """Server samples K clients without replacement."""
    return jax.random.choice(key, num_clients, (clients_per_round,), replace=False)


def _client_masks(client_sizes, n_pad: int):
    idx = jnp.arange(n_pad)[None, :]
    return (idx < client_sizes[:, None]).astype(F32)


def client_local_steps(loss_fn, params, client_lr: float, local_steps: int,
                       *, prox_mu: float = 0.0, correction=None):
    """Run a client's local plain-GD steps (paper: lr 1.0, 1 step).

    Returns (delta in f32, first-step loss). Shared by every round body —
    fed_sim and the sharded engine path — so the update rule has one home.

    Drift correction hooks (repro.server.drift):
      ``prox_mu``    — FedProx: the proximal gradient
                       ``mu * (p_local - p_broadcast)`` is added analytically
                       each step. ``prox_mu = 0`` (static) skips the term
                       entirely — bit-identical to the plain step (tested).
      ``correction`` — SCAFFOLD: a params-shaped pytree (``c - c_k``) added
                       to every local gradient; ``None`` skips it.
    """
    p_local = params
    loss0 = jnp.zeros((), F32)
    for step in range(local_steps):
        loss_val, g = jax.value_and_grad(loss_fn)(p_local)
        if step == 0:
            loss0 = loss_val
        if prox_mu:
            g = jax.tree.map(
                lambda g_, p_, p0: g_.astype(F32) + prox_mu * (
                    p_.astype(F32) - p0.astype(F32)), g, p_local, params)
        if correction is not None:
            g = jax.tree.map(lambda g_, c_: g_.astype(F32) + c_,
                             g, correction)
        p_local = jax.tree.map(
            lambda p_, g_: (p_.astype(F32)
                            - client_lr * g_.astype(F32)).astype(p_.dtype),
            p_local, g)
    delta = utils.tree_sub(utils.tree_cast(p_local, F32),
                           utils.tree_cast(params, F32))
    return delta, loss0


def check_variate_noise(channel) -> None:
    """A noising channel (DP) that does not noise the ``"variate"`` phase
    would release the aggregated SCAFFOLD variate delta — a deterministic
    clipped function of every client's raw update — un-noised while the
    accountant still reports a finite epsilon. Refuse the combination
    loudly (same contract as the engine's fedavg+stats-only guard)."""
    noise_phases = getattr(channel, "noise_phases", None)
    if noise_phases is not None and "variate" not in noise_phases:
        raise ValueError(
            f"{channel!r} noises only {noise_phases}, but SCAFFOLD ships "
            f"per-client variate deltas too — construct it with "
            f"noise_phases including 'variate' so the epsilon it reports "
            f"covers everything it releases")


def _scaffold_round_tail(scaffold_state, deltas, client_lr, local_steps,
                         w, ctx, channel):
    """Shared SCAFFOLD round tail: refresh slot variates from the *raw*
    client deltas (the refresh is client-side — it never crosses the wire),
    ship the variate deltas through the channel's ``"variate"`` phase, and
    fold the aggregate into the carried state.

    Returns (new ScaffoldState, extra uplink bytes)."""
    c_slots_new = drift_lib.scaffold_new_slot_variates(
        scaffold_state, deltas, client_lr, local_steps)
    dc = jax.tree.map(lambda new, old: new - old,
                      c_slots_new, scaffold_state.c_slots)
    if ctx is None:
        agg_dc = jax.tree.map(lambda d: jnp.tensordot(w, d, axes=1), dc)
        extra, pmask = 0.0, None
    else:
        agg_dc = channel.aggregate(ctx, dc, "variate")
        extra, pmask = channel.round_bytes(ctx, agg_dc), ctx.mask
    return drift_lib.scaffold_apply_round(
        scaffold_state, c_slots_new, agg_dc, pmask), extra


# ---------------------------------------------------------------------------
# two-phase stats round (paper Sec 3.3, Fig. 2 — generic over StatsObjective)
# ---------------------------------------------------------------------------

def stats_round(encoder_apply: Callable, params, opt_state, server_opt,
                client_data, client_sizes, *, objective,
                client_lr: float = 1.0, local_steps: int = 1,
                agg_stats_fn: Optional[Callable] = None,
                channel=None, channel_key=None,
                prox_mu: float = 0.0, scaffold_state=None):
    """One two-phase aggregated-statistics round for any StatsObjective
    (``repro.objectives``: dcco / dvicreg / dwmse / registered custom).
    Returns (params, opt_state, metrics). ``dcco_round`` is the CCO-bound
    back-compat alias.

    The protocol is objective-agnostic: phase 1 aggregates whatever stats
    dict ``objective.stats_masked`` emits (Eq. 3 applies because the
    protocol requires linearity in samples), phase 2 optimizes
    ``objective.loss_from_stats`` on the stop-grad combine, and any comm
    ``channel`` transports the objective's stats dict unchanged — payload
    shapes differ per objective (5 vs 7 stats) and quantization / DP /
    dropout / wire-bytes accounting compose per leaf.

    ``agg_stats_fn(zf_flat, zg_flat, mask_flat) -> Stats``, if given, computes
    the phase-1 *aggregate* statistics in one pass over the flattened cohort
    encodings. By Eq. 3 (stats are linear in samples) this equals the weighted
    average of per-client stats exactly — it is how the engine routes phase 1
    through the fused ``cco_stats_pallas`` kernel (with the objective's
    moment set). Phase 1 is never differentiated, so a non-differentiable
    kernel is safe here. The flat path requires a lossless
    full-participation channel (``channel.supports_flat_stats``) since
    per-client payloads never materialize.

    ``channel`` (repro.comm) routes both uplinks — phase-1 statistics and
    phase-2 deltas — through an explicit wire: participation mask and
    aggregation weights come from ``channel.begin_round(channel_key, ...)``,
    payloads go through the channel's encode/decode, and
    ``metrics.wire_bytes`` reports the round's uplink bytes. With
    ``channel=None`` (default) the legacy lossless path runs unchanged;
    DenseChannel is bit-identical to it (tested).

    ``server_opt`` may be a :class:`repro.optim.Optimizer` (wrapped as the
    bit-identical ``fedavg_sgd`` delegate) or any
    :class:`repro.server.ServerUpdate` strategy (FedAvgM / FedAdam / ...).

    Drift correction: ``prox_mu`` adds the FedProx proximal term to every
    local step (``0.0`` = statically off, bit-identical). Passing a
    ``scaffold_state`` (:class:`repro.server.ScaffoldState`) enables
    SCAFFOLD control variates; the round then returns a **4-tuple**
    ``(params, opt_state, new_scaffold_state, metrics)`` instead of the
    usual 3-tuple, and the per-slot variate deltas ride the channel's
    ``"variate"`` phase (accounted in ``metrics.wire_bytes``).
    """
    server_update = server_update_lib.as_server_update(server_opt)
    if scaffold_state is not None and channel is not None:
        check_variate_noise(channel)
    n_pad = jax.tree.leaves(client_data)[0].shape[1]
    masks = _client_masks(client_sizes, n_pad)               # (K, n)
    if channel is None:
        ctx = None
        w = client_sizes.astype(F32) / jnp.sum(client_sizes.astype(F32))
    else:
        if channel_key is None:
            raise ValueError("channel requires channel_key")
        ctx = channel.begin_round(channel_key, client_sizes)
        w = ctx.weights
    wire = 0.0

    # ---- phase 1: clients compute local stats; server aggregates (Eq. 3)
    if agg_stats_fn is None:
        def client_stats(batch, mask):
            zf, zg = encoder_apply(params, batch)
            return objective.stats_masked(zf, zg, mask)

        with jax.named_scope(phases.PHASE1):
            st_k = jax.vmap(client_stats)(client_data, masks)
            if ctx is None:
                agg = cco.weighted_average_stats(st_k,
                                                 client_sizes.astype(F32))
            else:
                agg = channel.aggregate(ctx, st_k, "stats")
    else:
        if ctx is not None and not channel.supports_flat_stats:
            raise ValueError(
                f"agg_stats_fn needs per-client payloads, incompatible "
                f"with {channel!r}")
        with jax.named_scope(phases.PHASE1):
            zf_k, zg_k = jax.vmap(lambda b: encoder_apply(params, b))(
                client_data)
            agg = agg_stats_fn(zf_k.reshape(-1, zf_k.shape[-1]),
                               zg_k.reshape(-1, zg_k.shape[-1]),
                               masks.reshape(-1))
    if ctx is not None:
        wire = wire + channel.round_bytes(ctx, agg)

    # ---- phase 2: server redistributes agg stats; clients run local steps
    def client_update(batch, mask, corr=None):
        def loss_fn(p):
            zf, zg = encoder_apply(p, batch)
            local = objective.stats_masked(zf, zg, mask)
            combined = objective.combine(local, agg)
            return objective.loss_from_stats(combined)

        return client_local_steps(loss_fn, params, client_lr, local_steps,
                                  prox_mu=prox_mu, correction=corr)

    with jax.named_scope(phases.PHASE2):
        if scaffold_state is None:
            deltas, losses_k = jax.vmap(client_update)(client_data, masks)
        else:
            deltas, losses_k = jax.vmap(client_update)(
                client_data, masks,
                drift_lib.scaffold_corrections(scaffold_state))

    # ---- server: weighted average of deltas -> FedOpt pseudo-gradient
    with jax.named_scope(phases.DELTA_AVERAGE):
        if ctx is None:
            avg_delta = jax.tree.map(lambda d: jnp.tensordot(w, d, axes=1),
                                     deltas)
        else:
            avg_delta = channel.aggregate(ctx, deltas, "update")
            wire = wire + channel.round_bytes(ctx, avg_delta)
    with jax.named_scope(phases.SERVER_UPDATE):
        params, opt_state = server_update.step(params, opt_state, avg_delta)

    # collapse probe on the aggregated stats
    enc_std = objective.encoding_std(agg)
    if scaffold_state is not None:
        new_scaffold, extra = _scaffold_round_tail(
            scaffold_state, deltas, client_lr, local_steps, w, ctx, channel)
        metrics = RoundMetrics(jnp.sum(w * losses_k), enc_std,
                               jnp.asarray(wire + extra, F32))
        return params, opt_state, new_scaffold, metrics
    return params, opt_state, RoundMetrics(jnp.sum(w * losses_k), enc_std,
                                           jnp.asarray(wire, F32))


def dcco_round(encoder_apply: Callable, params, opt_state, server_opt,
               client_data, client_sizes, *, lam: float = 20.0,
               objective=None, **round_kw):
    """Back-compat alias: one DCCO round == ``stats_round`` with the CCO
    objective (``lam`` is CCO's off-diagonal weight). See ``stats_round``
    for the full contract; passing ``objective=`` selects another
    registered stats objective (then ``lam`` is ignored)."""
    return stats_round(encoder_apply, params, opt_state, server_opt,
                       client_data, client_sizes,
                       objective=resolve_objective(objective, lam),
                       **round_kw)


# ---------------------------------------------------------------------------
# FedAvg baselines (within-client loss, no stats exchange)
# ---------------------------------------------------------------------------

def fedavg_round(encoder_apply: Callable, params, opt_state, server_opt,
                 client_data, client_sizes, *, loss_kind: str = "cco",
                 lam: float = 20.0, temperature: float = 0.1,
                 objective=None,
                 client_lr: float = 1.0, local_steps: int = 1,
                 channel=None, channel_key=None,
                 prox_mu: float = 0.0, scaffold_state=None):
    """FedAvg with a within-client loss: 'stats' | 'cco' | 'contrastive'
    | 'byol'.

    The ``'stats'`` kind runs any :class:`repro.objectives.StatsObjective`
    as a *within-client* loss (no stats exchange — the baseline DCCO-style
    training is compared against); ``'cco'`` is its back-compat spelling
    bound to the CCO objective with ``lam``, so the historical path is
    bit-identical.

    ``channel`` routes the single uplink (client deltas) through the wire,
    same contract as in ``stats_round`` — as are ``server_opt`` (Optimizer
    or ServerUpdate), ``prox_mu``, and ``scaffold_state`` (which again
    turns the return into a 4-tuple carrying the new variates).
    """
    server_update = server_update_lib.as_server_update(server_opt)
    if loss_kind in ("cco", "stats"):
        objective = resolve_objective(objective, lam)
    if scaffold_state is not None and channel is not None:
        check_variate_noise(channel)
    n_pad = jax.tree.leaves(client_data)[0].shape[1]
    masks = _client_masks(client_sizes, n_pad)
    if channel is None:
        ctx = None
        w = client_sizes.astype(F32) / jnp.sum(client_sizes.astype(F32))
    else:
        if channel_key is None:
            raise ValueError("channel requires channel_key")
        ctx = channel.begin_round(channel_key, client_sizes)
        w = ctx.weights

    def client_loss(p, batch, mask):
        zf, zg = encoder_apply(p, batch)
        if loss_kind in ("cco", "stats"):
            st = objective.stats_masked(zf, zg, mask)
            return objective.loss_from_stats(st)
        if loss_kind == "contrastive":
            # NOTE: padding samples contribute as (weak) negatives; paper's
            # clients are tiny so we keep the simple masked-mean variant.
            return losses.ntxent_loss(zf, zg, temperature)
        if loss_kind == "byol":
            return losses.byol_predictive_loss(zf, zg)
        raise ValueError(loss_kind)

    def client_update(batch, mask, corr=None):
        return client_local_steps(lambda p: client_loss(p, batch, mask),
                                  params, client_lr, local_steps,
                                  prox_mu=prox_mu, correction=corr)

    if scaffold_state is None:
        deltas, losses_k = jax.vmap(client_update)(client_data, masks)
    else:
        deltas, losses_k = jax.vmap(client_update)(
            client_data, masks, drift_lib.scaffold_corrections(scaffold_state))
    if ctx is None:
        avg_delta = jax.tree.map(lambda d: jnp.tensordot(w, d, axes=1), deltas)
        wire = 0.0
    else:
        avg_delta = channel.aggregate(ctx, deltas, "update")
        wire = channel.round_bytes(ctx, avg_delta)
    params, opt_state = server_update.step(params, opt_state, avg_delta)
    if scaffold_state is not None:
        new_scaffold, extra = _scaffold_round_tail(
            scaffold_state, deltas, client_lr, local_steps, w, ctx, channel)
        metrics = RoundMetrics(jnp.sum(w * losses_k), jnp.zeros((), F32),
                               jnp.asarray(wire + extra, F32))
        return params, opt_state, new_scaffold, metrics
    return params, opt_state, RoundMetrics(jnp.sum(w * losses_k),
                                           jnp.zeros((), F32),
                                           jnp.asarray(wire, F32))


# ---------------------------------------------------------------------------
# Centralized step (the paper's upper bound) — for equivalence checks
# ---------------------------------------------------------------------------

def centralized_step(encoder_apply: Callable, params, opt_state, server_opt,
                     batch, mask=None, *, lam: float = 20.0, objective=None):
    """One centralized large-batch step of a stats objective (default: CCO
    with ``lam`` — the pre-protocol behavior). batch leaves: (N, ...).

    ``server_opt`` may be an Optimizer or a ServerUpdate; the raw gradient
    goes straight to the wrapped optimizer (there is no client delta here,
    so drift corrections do not apply)."""
    server_opt = server_update_lib.as_server_update(server_opt).opt
    objective = resolve_objective(objective, lam)

    def loss_fn(p):
        zf, zg = encoder_apply(p, batch)
        if mask is not None:
            st = objective.stats_masked(zf, zg, mask)
        else:
            st = objective.stats(zf, zg)
        return objective.loss_from_stats(st)

    loss, g = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = server_opt.update(g, opt_state, params)
    params = opt_lib.apply_updates(params, updates)
    return params, opt_state, RoundMetrics(loss, jnp.zeros((), F32))
