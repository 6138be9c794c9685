"""DCCO loss paths for the pod-scale (single-program) train step.

Three implementations, all gradient-equivalent (tested):

  fused      — centralized-equivalent: CCO on the differentiable global batch
               statistics. By the paper's Appendix-A theorem this equals one
               DCCO round with one local step, at the cost of ZERO extra
               collectives beyond the stats all-reduce XLA already inserts
               for the batch-mean. This is the optimized production path.

  per_client — faithful per-client formulation: per-client stats, weighted
               aggregate, stop-grad combine per client, weighted per-client
               losses. Mirrors the protocol math exactly (gradients provably
               identical; see tests/test_equivalence.py).

  shard_map  — protocol-faithful at the *device* level: each (pod,data) shard
               plays a client cohort; local stats -> explicit psum over the
               data axes (the wire aggregation of Fig. 2) -> stop-grad
               combine -> loss. Used to demonstrate/measure the protocol's
               collective on the mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import cco

F32 = jnp.float32


def dcco_loss_fused(zf, zg, lam: float) -> jnp.ndarray:
    return cco.cco_loss(zf, zg, lam)


def dcco_loss_per_client(zf, zg, lam: float, clients: int) -> jnp.ndarray:
    """Faithful per-client DCCO objective (equal-size clients).

    L = sum_k (N_k/N) L_CCO(<.>_k + sg(<.>_A - <.>_k))
    """
    st_k = cco.per_client_stats(zf, zg, clients)             # stacked (K, ...)
    w = jnp.full((clients,), 1.0 / clients, F32)
    agg = cco.weighted_average_stats(st_k, w)

    def client_loss(stats_k):
        return cco.cco_loss_from_stats(cco.dcco_combine(stats_k, agg), lam)

    losses = jax.vmap(client_loss)(st_k)
    return jnp.sum(w * losses)


def dcco_loss_shard_map_local(zf_local, zg_local, lam: float, axis_names) -> jnp.ndarray:
    """Body to be run under shard_map: zf/zg are the LOCAL shard's encodings.

    Computes local stats, aggregates across `axis_names` with an explicit
    psum (the DCCO wire protocol), applies the stop-grad combine, and
    returns the local loss (identical value on all shards).
    """
    local = cco.encoding_stats(zf_local, zg_local)
    # equal shard sizes -> weighted average = pmean
    agg = {k: jax.lax.pmean(v, axis_names) for k, v in local.items()}
    combined = cco.dcco_combine(local, agg)
    return cco.cco_loss_from_stats(combined, lam)


def make_shard_map_dcco_loss(mesh, lam: float, data_axes=("data",)):
    """Returns loss_fn(zf, zg) where zf/zg are batch-sharded global arrays.

    Note the gradient: each shard backprops through its local stats only;
    psum of the per-shard grads (inserted by shard_map's transpose) yields
    exactly the centralized gradient — Appendix A at device granularity.
    """
    pspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None)

    # replication checking off: the body replicates its output via psum
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspec, pspec), out_specs=P(), check_vma=False)
    def loss_fn(zf, zg):
        loss = dcco_loss_shard_map_local(zf, zg, lam, data_axes)
        return loss[None] if loss.ndim == 0 else loss

    def wrapped(zf, zg):
        out = loss_fn(zf, zg)
        return out.reshape(())

    return wrapped


def dcco_loss(zf, zg, lam: float, impl: str = "fused", clients: int = 0,
              mesh=None, data_axes=("data",)):
    if impl == "fused":
        return dcco_loss_fused(zf, zg, lam)
    if impl == "per_client":
        assert clients > 0
        return dcco_loss_per_client(zf, zg, lam, clients)
    if impl == "shard_map":
        assert mesh is not None
        return make_shard_map_dcco_loss(mesh, lam, data_axes)(zf, zg)
    raise ValueError(f"unknown dcco impl {impl}")
