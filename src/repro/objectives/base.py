"""StatsObjective — the protocol behind every stats-based federated loss.

The paper's core insight (Eq. 3) is that any loss computable from
encoding statistics that are *linear in samples* can be trained
federatedly by aggregating those statistics: large-batch statistics are
exactly the client-size-weighted average of per-client statistics, so the
two-phase aggregate / redistribute / stop-grad-combine protocol (Fig. 2)
— and the Appendix-A centralized-equivalence — apply to the whole family,
not just CCO. Sec. 6 names VICReg as the first extension; this module
makes the family a first-class protocol.

A :class:`StatsObjective` declares

  * its stat spec — which statistics ride the wire (``stat_keys``,
    ``stat_spec``) and whether the within-view second moments are among
    them (``second_moments``, the kernel's moment-set flag);
  * ``stats`` / ``stats_masked`` — accumulation through the ONE shared
    accumulator (:func:`repro.core.cco.moment_stats`), required linear in
    samples so Eq.-3 aggregation, the flattened-cohort
    ``cco_stats_pallas`` path, and the shard_map psum path all stay
    exact;
  * ``loss_from_stats`` — the loss as a pure function of statistics;
  * ``combine`` — the stop-grad combine ``<.>_k + sg(<.>_A - <.>_k)``
    (paper Fig. 2; shared default).

Everything downstream — ``fed_sim.stats_round``, the engine bodies,
``stats_round_sharded``, the comm Channels, the train CLI, and the
benchmarks — is parametric in the objective: the channels transport the
objective's stats dict unchanged (payload shapes differ per objective;
quantization / DP / dropout and wire-bytes accounting compose per leaf),
and the gradient-equivalence tests run per registered objective.

Precision contract: statistics ACCUMULATE in float32 regardless of the
encoder's compute dtype. ``cco.moment_stats`` casts its inputs to f32
before any reduction, so a bf16 encoder forward
(``EngineConfig.compute_dtype='bfloat16'``) feeds f32 sums — this is
what keeps Eq.-3 exact under mixed precision: the aggregation is a sum
over the whole cohort (N up to tens of thousands of samples), and bf16's
8-bit mantissa would lose low-order per-sample contributions long before
the cohort is fully accumulated, silently biasing ``sq_*``/``cross``
(and thus every loss in the family) toward the large-magnitude samples.
Tests pin both halves: accumulator dtype is f32 for bf16 inputs, and
bf16-input stats stay within bf16-rounding tolerance of f32 stats.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import cco

F32 = jnp.float32
Stats = Dict[str, jnp.ndarray]


class StatsObjective:
    """A dual-encoding loss computable from linear-in-samples statistics.

    Subclasses set ``name``, ``stat_keys``, ``second_moments``, and
    implement ``loss_from_stats``; the accumulation, combine, spec, and
    collapse-probe plumbing is shared.
    """

    name: str = "stats"
    stat_keys: Tuple[str, ...] = cco.STAT_KEYS
    second_moments: bool = False

    # ------------------------------------------------------- accumulation
    def stats(self, zf, zg) -> Stats:
        """Batch statistics of encodings zf, zg: (N, d) -> Stats."""
        return cco.moment_stats(zf, zg, second_moments=self.second_moments)

    def stats_masked(self, zf, zg, mask) -> Stats:
        """Statistics over valid samples only (mask: (N,) in {0,1})."""
        return cco.moment_stats(zf, zg, mask,
                                second_moments=self.second_moments)

    def stat_spec(self, d: int) -> Dict[str, Tuple[int, ...]]:
        """Wire payload spec: stat key -> shape, for encoding dim ``d``.

        Derived from ``stats`` itself via ``jax.eval_shape`` (no FLOPs, no
        memory), so custom registered objectives with their own stat keys
        get a correct spec with no override."""
        z = jax.ShapeDtypeStruct((1, d), F32)
        return {k: tuple(v.shape)
                for k, v in jax.eval_shape(self.stats, z, z).items()}

    def stat_template(self, d: int) -> Stats:
        """Zero payload pytree matching ``stat_spec`` (bytes accounting)."""
        return {k: jnp.zeros(s, F32) for k, s in self.stat_spec(d).items()}

    # ------------------------------------------------------ loss + combine
    def loss_from_stats(self, st: Stats) -> jnp.ndarray:
        raise NotImplementedError

    def combine(self, local: Stats, agg: Stats) -> Stats:
        """Stop-grad combine <.>_C = <.>_k + sg(<.>_A - <.>_k) (Fig. 2)."""
        return cco.dcco_combine(local, agg)

    def loss(self, zf, zg) -> jnp.ndarray:
        """Centralized large-batch loss (the paper's upper-bound baseline)."""
        return self.loss_from_stats(self.stats(zf, zg))

    # ------------------------------------------------------------- probes
    def encoding_std(self, agg: Stats) -> jnp.ndarray:
        """Collapse probe on aggregated stats (mean per-dim std of F)."""
        return jnp.sqrt(jnp.maximum(
            agg["sq_f"] - agg["mean_f"] ** 2, 0.0)).mean()

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


def per_client_loss(objective: StatsObjective, zf, zg,
                    clients: int) -> jnp.ndarray:
    """Faithful per-client federated objective for any StatsObjective.

    L = sum_k (N_k/N) L(<.>_k + sg(<.>_A - <.>_k)) with equal-size
    clients laid out contiguously — the generic form of
    ``dcco.dcco_loss_per_client`` / the old ``dvicreg_loss_per_client``.
    Gradient-equivalent to the centralized ``objective.loss`` by the
    Appendix-A argument (tested per registered objective).
    """
    n, d = zf.shape
    assert n % clients == 0
    st_k = jax.vmap(objective.stats)(zf.reshape(clients, n // clients, d),
                                     zg.reshape(clients, n // clients, d))
    w = jnp.full((clients,), 1.0 / clients, F32)
    agg = cco.weighted_average_stats(st_k, w)

    def client_loss(stats_k):
        return objective.loss_from_stats(objective.combine(stats_k, agg))

    return jnp.sum(w * jax.vmap(client_loss)(st_k))


def make_shard_map_loss(objective: StatsObjective, mesh,
                        data_axes=("data",)):
    """Shard_map loss for any StatsObjective: local stats -> explicit psum
    aggregation over ``data_axes`` (the Fig.-2 wire collective at device
    granularity) -> stop-grad combine -> loss. Generic form of
    ``dcco.make_shard_map_dcco_loss``; gradients match the centralized
    loss exactly (shard_map's transpose psums the per-shard grads)."""
    from jax.sharding import PartitionSpec as P

    pspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None)

    def local_loss(zf_local, zg_local):
        local = objective.stats(zf_local, zg_local)
        agg = {k: jax.lax.pmean(v, data_axes) for k, v in local.items()}
        loss = objective.loss_from_stats(objective.combine(local, agg))
        return loss[None] if loss.ndim == 0 else loss

    sharded = jax.shard_map(local_loss, mesh=mesh, in_specs=(pspec, pspec),
                            out_specs=P(), check_vma=False)

    def wrapped(zf, zg):
        return sharded(zf, zg).reshape(())

    return wrapped
