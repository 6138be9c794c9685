"""Two-level aggregation topology: clients → edge aggregators → server.

Cross-device federated populations (McMahan et al., 2017) do not report to
one server socket: clients upload to regional *edge aggregators*, which
forward partial aggregates upstream. Because every payload the stats
protocol ships is linear in samples (paper Eq. 3), aggregation is exact
under ANY summation tree — the edge hop changes the wire, not the math.

:class:`HierarchicalChannel` makes that tree a drop-in
:class:`repro.comm.Channel`: it composes two hop channels,

    clients --client_channel--> edges --edge_channel--> server

so e.g. the bandwidth-starved client uplink runs int8 quantization while
the edge→server backbone stays dense, an edge-hop ``DropoutChannel``
models a regional outage (every client behind the edge vanishes at once),
and ``wire_bytes`` accounts both hops (K client payloads + E edge
payloads per round).

Exactness contract:

  * **ideal hops collapse** — when both hops are ideal identity wires
    (``Channel.ideal``), the two-level tree equals the flat weighted sum
    *in math*, so the aggregate is computed AS the flat sum: bit-identical
    (``== 0.0``) to the un-channeled / DenseChannel paths for every
    registered objective (tested), which keeps engine regression baselines
    and resume streams byte-stable. ``collapse_ideal=False`` forces the
    real tree (used by tests to show the regrouping is float-level only).
  * **lossy hops run the real tree** — encode/decode is not linear, so the
    fold happens where the protocol says it does: per-client encode on the
    client hop, a one-pass segment-sum fold of w_k·payload_k into per-edge
    partials (``kernels/segment_sum.py`` when ``fold_impl`` selects the
    Pallas kernel), per-edge encode on the edge hop, then the server sum.

DP hops are refused loudly: calibrating per-hop Gaussian noise and keeping
the epsilon accountant honest across a two-level tree is its own design
problem (per-edge sensitivity, noise composition across aggregators), and
a silently mis-calibrated epsilon is worse than no DP — same contract as
``fed_sim.check_variate_noise``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comm.channel import Channel, ChannelContext, DenseChannel
from repro.kernels import ref as kernels_ref
from repro.kernels.ops import on_tpu
from repro.kernels.segment_sum import segment_sum_pallas

F32 = jnp.float32

# fold_in salt deriving the edge hop's shard-local randomness from a
# shard-folded client key in the sharded local_fold path
_EDGE_SALT = 0xED6E

FOLD_IMPLS = ("jnp", "pallas", "interpret")


def contiguous_edge_ids(num_clients: int, num_edges: int) -> jnp.ndarray:
    """Edge assignment: client k reports to edge k // (K/E) — contiguous
    equal-size groups, the layout that aligns with cohort chunks and with
    the sharded client axis. Requires K % E == 0 (static)."""
    if num_clients % num_edges:
        raise ValueError(
            f"cohort of {num_clients} clients does not divide into "
            f"{num_edges} equal edges")
    return jnp.arange(num_clients, dtype=jnp.int32) // (
        num_clients // num_edges)


def fold_to_edges(tree_k, weights, seg_ids, num_edges: int,
                  impl: str = "jnp"):
    """Fold stacked per-client payloads (leading axis K) into per-edge
    partial sums (leading axis E): out[e] = sum_{k in e} w_k * leaf[k].

    All leaves are flattened and concatenated into ONE (K, D) row matrix
    so the whole stats dict folds in a single pass — the jnp path through
    ``jax.ops.segment_sum``, the kernel paths through
    ``segment_sum_pallas`` (``"pallas"`` runs under the interpreter off
    the TPU, same policy as the engine's stats_kernel flag)."""
    if impl not in FOLD_IMPLS:
        raise ValueError(f"unknown fold impl {impl!r}; "
                         f"expected one of {FOLD_IMPLS}")
    leaves, treedef = jax.tree.flatten(tree_k)
    k = leaves[0].shape[0]
    shapes = [leaf.shape[1:] for leaf in leaves]
    rows = jnp.concatenate(
        [leaf.astype(F32).reshape(k, -1) for leaf in leaves], axis=1)
    if impl == "jnp":
        folded = kernels_ref.segment_sum_ref(rows, seg_ids, num_edges,
                                             weights)
    else:
        interpret = impl == "interpret" or not on_tpu()
        folded = segment_sum_pallas(rows, seg_ids, num_edges, weights,
                                    interpret=interpret)
    out, off = [], 0
    for shp in shapes:
        size = 1
        for s in shp:
            size *= s
        out.append(folded[:, off:off + size].reshape((num_edges,) + shp))
        off += size
    return jax.tree.unflatten(treedef, out)


class HierarchicalContext(NamedTuple):
    """Composite per-round context. The first four fields mirror
    :class:`repro.comm.ChannelContext` (mask/weights are the *effective*
    per-client values with the edge hop folded in), so every consumer of a
    plain context — fed_sim's loss weighting, the scaffold tail, the
    sharded extra-arg plumbing — works unchanged."""
    key: jnp.ndarray
    mask: jnp.ndarray                  # (K,) — client mask x edge mask
    weights: jnp.ndarray               # (K,) — edge-masked, renormalized
    num_participants: jnp.ndarray      # f32 — surviving clients
    client_ctx: ChannelContext
    edge_ctx: ChannelContext
    edge_ids: jnp.ndarray              # (K,) int32 — client -> edge


class HierarchicalChannel(Channel):
    """Two-level aggregation tree as a pluggable comm Channel."""

    name = "hierarchical"

    def __init__(self, num_edges: int,
                 client_channel: Optional[Channel] = None,
                 edge_channel: Optional[Channel] = None,
                 fold_impl: str = "jnp", collapse_ideal: bool = True):
        if num_edges < 1:
            raise ValueError(f"num_edges must be >= 1, got {num_edges}")
        if fold_impl not in FOLD_IMPLS:
            raise ValueError(f"unknown fold impl {fold_impl!r}; "
                             f"expected one of {FOLD_IMPLS}")
        self.num_edges = int(num_edges)
        self.client_channel = client_channel or DenseChannel()
        self.edge_channel = edge_channel or DenseChannel()
        self.fold_impl = fold_impl
        for hop_name, hop in (("client", self.client_channel),
                              ("edge", self.edge_channel)):
            if isinstance(hop, HierarchicalChannel):
                raise ValueError(
                    f"nested hierarchical {hop_name} hop: flatten the tree "
                    f"into one client->edge->server topology instead")
            if getattr(hop, "noise_phases", None) is not None:
                raise ValueError(
                    f"{hop!r} as the {hop_name} hop: DP noise calibration "
                    f"and epsilon accounting across a two-level tree are "
                    f"not defined here — run the DP channel flat, or add a "
                    f"hierarchy-aware accountant first")
        # both hops ideal -> the tree is the flat sum in math; compute it
        # as the flat sum so the result is bit-identical to the
        # un-channeled paths (and the flat-cohort stats kernel stays exact)
        self.collapses = bool(collapse_ideal and self.client_channel.ideal
                              and self.edge_channel.ideal)
        self.supports_flat_stats = self.collapses
        self.full_participation = (self.client_channel.full_participation
                                   and self.edge_channel.full_participation)

    # ------------------------------------------------------------ round --
    def begin_round(self, key, client_sizes) -> HierarchicalContext:
        k = client_sizes.shape[0]
        edge_ids = contiguous_edge_ids(k, self.num_edges)
        k_client, k_edge = jax.random.split(key)
        cctx = self.client_channel.begin_round(k_client, client_sizes)
        # per-edge mass of *reporting* clients drives the edge hop's sizes
        edge_mass = kernels_ref.segment_sum_ref(
            (client_sizes.astype(F32) * cctx.mask)[:, None], edge_ids,
            self.num_edges)[:, 0]
        ectx = self.edge_channel.begin_round(k_edge, edge_mass)
        if self.edge_channel.full_participation:
            # all-ones edge mask: the client hop's weights are already the
            # effective weights — reuse them untouched (bitwise, so the
            # ideal-ideal collapse stays == the flat dense path)
            mask, weights = cctx.mask, cctx.weights
            num = cctx.num_participants
        else:
            keep = ectx.mask[edge_ids]                       # (K,)
            mask = cctx.mask * keep
            w_raw = cctx.weights * keep
            weights = w_raw / jnp.maximum(jnp.sum(w_raw), 1e-12)
            num = jnp.sum(mask)
        return HierarchicalContext(key, mask, weights, num, cctx, ectx,
                                   edge_ids)

    def with_edge_ids(self, ctx: HierarchicalContext,
                      edge_ids) -> HierarchicalContext:
        """Re-route the round through a SEMANTIC edge assignment (e.g.
        the clustered engine's in-scan cluster ids, repro.cluster) instead
        of the contiguous topological one: the edge hop re-runs
        ``begin_round`` on the new per-edge mass with the same edge key,
        and the effective mask/weights are recomposed exactly as
        ``begin_round`` composes them. ``edge_ids`` may be traced (it is
        computed inside the scan), so no K % E divisibility is assumed —
        an edge may legitimately be empty this round."""
        _, k_edge = jax.random.split(ctx.key)
        cctx = ctx.client_ctx
        # the client hop's masked weights stand in for sizes (proportional
        # — the edge hop only normalizes its per-edge mass)
        mass = kernels_ref.segment_sum_ref(
            (cctx.weights * cctx.mask)[:, None], edge_ids,
            self.num_edges)[:, 0]
        ectx = self.edge_channel.begin_round(k_edge, mass)
        if self.edge_channel.full_participation:
            mask, weights, num = cctx.mask, cctx.weights, \
                cctx.num_participants
        else:
            keep = ectx.mask[edge_ids]                       # (K,)
            mask = cctx.mask * keep
            w_raw = cctx.weights * keep
            weights = w_raw / jnp.maximum(jnp.sum(w_raw), 1e-12)
            num = jnp.sum(mask)
        return ctx._replace(mask=mask, weights=weights,
                            num_participants=num, edge_ctx=ectx,
                            edge_ids=jnp.asarray(edge_ids, jnp.int32))

    # ------------------------------------------------------------- wire --
    def _client_view(self, ctx) -> ChannelContext:
        """The client hop's view of a context: the composite's sub-context
        when available, the plain context itself otherwise (the sharded
        body reconstructs plain contexts from sliced arrays)."""
        if isinstance(ctx, HierarchicalContext):
            return ctx.client_ctx._replace(mask=ctx.mask,
                                           weights=ctx.weights)
        return ctx

    def encode_decode(self, ctx, tree_k, phase: str):
        return self.client_channel.encode_decode(self._client_view(ctx),
                                                 tree_k, phase)

    def post_aggregate(self, ctx, tree, phase: str):
        if isinstance(ctx, HierarchicalContext):
            return self.edge_channel.post_aggregate(ctx.edge_ctx, tree,
                                                    phase)
        return tree

    def aggregate(self, ctx: HierarchicalContext, tree_k, phase: str):
        if self.collapses:
            return self.client_channel.aggregate(self._client_view(ctx),
                                                 tree_k, phase)
        dec = self.client_channel.encode_decode(ctx.client_ctx, tree_k,
                                                phase)
        partials = fold_to_edges(dec, ctx.weights, ctx.edge_ids,
                                 self.num_edges, self.fold_impl)
        enc = self.edge_channel.encode_decode(ctx.edge_ctx, partials, phase)
        agg = jax.tree.map(
            lambda v: jnp.tensordot(ctx.edge_ctx.mask, v, axes=1), enc)
        return self.edge_channel.post_aggregate(ctx.edge_ctx, agg, phase)

    def local_fold(self, ctx_local, dec_tree, phase: str, *,
                   num_shards: int = 1):
        """Sharded-cohort fold: edges align with the mesh — each shard
        folds its K/num_shards clients into its E/num_shards edges with
        the segment-sum kernel and runs the edge hop locally; the psum
        over shards (done by the caller) is the edge→server sum."""
        if self.collapses:
            return super().local_fold(ctx_local, dec_tree, phase)
        if self.num_edges % num_shards:
            raise ValueError(
                f"{self.num_edges} edges do not align with {num_shards} "
                f"shards: num_edges must be a multiple of the cohort mesh "
                f"axis size")
        e_local = self.num_edges // num_shards
        k_local = jax.tree.leaves(dec_tree)[0].shape[0]
        ids = contiguous_edge_ids(k_local, e_local)
        partials = fold_to_edges(dec_tree, ctx_local.weights, ids, e_local,
                                 self.fold_impl)
        ectx_l = ChannelContext(
            jax.random.fold_in(ctx_local.key, _EDGE_SALT),
            jnp.ones((e_local,), F32), jnp.full((e_local,), 1.0 / e_local,
                                                F32),
            jnp.asarray(float(e_local), F32))
        enc = self.edge_channel.encode_decode(ectx_l, partials, phase)
        return jax.tree.map(lambda v: jnp.sum(v, axis=0), enc)

    def chunk_fold(self, ctx: HierarchicalContext, tree_chunk, phase: str,
                   chunk_index, chunk_weights):
        """Streaming fold: the cohort chunk must hold whole edges (the
        engine validates chunk % (K/E) == 0 at build), so each chunk folds
        its clients into its own edges, runs the edge hop, and hands back
        a partial the streaming scan accumulates."""
        chunk = jax.tree.leaves(tree_chunk)[0].shape[0]
        k = ctx.weights.shape[0]
        edge_size = k // self.num_edges
        if chunk % edge_size:
            raise ValueError(
                f"cohort chunk of {chunk} does not hold whole edges "
                f"(edge size {edge_size}): pick cohort_chunk a multiple "
                f"of clients-per-round / num_edges")
        if self.collapses:
            return super().chunk_fold(ctx, tree_chunk, phase, chunk_index,
                                      chunk_weights)
        e_chunk = chunk // edge_size
        cctx_c = ctx.client_ctx._replace(
            key=jax.random.fold_in(ctx.client_ctx.key, chunk_index))
        dec = self.client_channel.encode_decode(cctx_c, tree_chunk, phase)
        partials = fold_to_edges(dec, chunk_weights,
                                 contiguous_edge_ids(chunk, e_chunk),
                                 e_chunk, self.fold_impl)
        ectx_c = ctx.edge_ctx._replace(
            key=jax.random.fold_in(ctx.edge_ctx.key, chunk_index))
        enc = self.edge_channel.encode_decode(ectx_c, partials, phase)
        emask = jax.lax.dynamic_slice(ctx.edge_ctx.mask,
                                      (chunk_index * e_chunk,), (e_chunk,))
        return jax.tree.map(lambda v: jnp.tensordot(emask, v, axes=1), enc)

    # ------------------------------------------------------- accounting --
    def round_bytes(self, ctx: HierarchicalContext, payload_template):
        per_hop = self.hop_bytes(ctx, payload_template)
        return per_hop["client_edge"] + per_hop["edge_server"]

    def hop_bytes(self, ctx: HierarchicalContext, payload_template):
        """Per-hop uplink bytes this round: surviving clients x the client
        hop's payload width, surviving edges x the edge hop's width."""
        return {
            "client_edge": ctx.num_participants *
            self.client_channel.payload_bytes(payload_template),
            "edge_server": ctx.edge_ctx.num_participants *
            self.edge_channel.payload_bytes(payload_template),
        }

    def payload_bytes(self, tree) -> float:
        # per-client wire width = the client hop's encoding
        return self.client_channel.payload_bytes(tree)

    def finalize_rounds(self, num_rounds: int) -> None:
        self.client_channel.finalize_rounds(num_rounds)
        self.edge_channel.finalize_rounds(num_rounds)

    def __repr__(self) -> str:
        return (f"HierarchicalChannel(edges={self.num_edges}, "
                f"client={self.client_channel!r}, "
                f"edge={self.edge_channel!r})")
