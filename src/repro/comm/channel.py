"""Pluggable client->server communication channels.

DCCO's premise is that clients ship *aggregate encoding statistics* rather
than raw samples (paper Eq. 3, Fig. 2), yet an idealized simulation models
that uplink as a free, lossless sum. A :class:`Channel` makes the wire
explicit: every per-client payload — phase-1 statistics, phase-2 parameter
deltas, FedAvg updates — flows through

    begin_round  ->  encode_decode (per client)  ->  weighted sum
                 ->  post_aggregate (server side)

with bytes-on-the-wire accounting. All channel math is pure traced jax
driven by an explicit PRNG key, so the dispatch is resolved at trace time
and the per-round work compiles INSIDE the engine's ``lax.scan`` — no
per-round Python cost.

Implementations:

  DenseChannel      — identity wire; bit-exact with the un-channeled paths
                      (tested), the baseline every other channel is
                      measured against.
  QuantizedChannel  — int-``bits`` stochastic-rounding encode/decode with
                      per-client per-tensor scales (repro.comm.quantize;
                      optionally the fused Pallas kernel).
  DPGaussianChannel — per-client L2 clipping + calibrated Gaussian noise on
                      the aggregate (uniform client weights — size-weighted
                      aggregation would leak private client sizes), with a
                      zCDP epsilon accountant.
  DropoutChannel    — Bernoulli client dropout with mask-renormalized
                      aggregation, so Eq. 3's normalizer runs over the
                      surviving cohort only; at p=0 it is bit-identical to
                      DenseChannel.

Aggregation semantics: ``aggregate(ctx, tree_k, phase)`` consumes a pytree
of stacked per-client payloads (leading axis K) and returns the weighted
average the protocol expects — for DenseChannel exactly
``cco.weighted_average_stats`` / the delta ``tensordot`` of fed_sim.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.accountant import GaussianAccountant
from repro.comm.quantize import (payload_bytes as quant_payload_bytes,
                                 qmax_for_bits, quant_dequant_payload)
from repro.kernels.ops import on_tpu

F32 = jnp.float32

# salts folded into the round key so the stats / update / variate phases
# draw independent randomness from one per-round channel key. "variate" is
# the SCAFFOLD control-variate uplink (repro.server.drift): per-client
# variate deltas are payloads like any other, so quantization / DP noise /
# dropout compose with drift correction and the bytes are accounted.
PHASE_SALT = {"stats": 0x57A75, "update": 0x0BDA7E, "variate": 0x5CAF0}


class ChannelContext(NamedTuple):
    """Per-round channel state, computed once by ``begin_round``."""
    key: jnp.ndarray               # per-round payload randomness
    mask: jnp.ndarray              # (K,) f32 — 1 for participating clients
    weights: jnp.ndarray           # (K,) f32 — normalized agg weights
    num_participants: jnp.ndarray  # f32 scalar = sum(mask)


def _leaf_keys(key, phase: str, num_leaves: int):
    return jax.random.split(jax.random.fold_in(key, PHASE_SALT[phase]),
                            max(num_leaves, 1))


class Channel:
    """Base channel: full participation, size-weighted lossless aggregation.

    Subclasses override any of ``begin_round`` (participation + weights),
    ``encode_decode`` (the per-client wire transform), ``post_aggregate``
    (server-side processing of the aggregate), and ``payload_bytes``
    (per-client wire cost of one payload).
    """

    name = "dense"
    # whether the engine may compute phase-1 aggregate stats from the
    # flattened cohort (the cco_stats kernel path) instead of per-client
    # payloads — only lossless, size-weighted, full-participation channels
    # qualify.
    supports_flat_stats = True
    # an *ideal* channel is a lossless identity wire with size-weighted
    # aggregation — exactly the un-channeled math. The hierarchical
    # aggregator (repro.hierarchy) collapses a tree of ideal hops to the
    # flat sum (bit-identical by Eq.-3 linearity). Deliberately False on
    # the base class: a custom subclass that forgets to think about it
    # only loses the fast path, never correctness.
    ideal = False
    # whether begin_round always returns an all-ones participation mask;
    # False lets the hierarchy know it must renormalize weights over the
    # surviving mass when this channel runs a hop.
    full_participation = True

    def begin_round(self, key, client_sizes) -> ChannelContext:
        k = client_sizes.shape[0]
        s = client_sizes.astype(F32)
        return ChannelContext(key, jnp.ones((k,), F32), s / jnp.sum(s),
                              jnp.asarray(float(k), F32))

    def encode_decode(self, ctx: ChannelContext, tree_k, phase: str):
        return tree_k

    def post_aggregate(self, ctx: ChannelContext, tree, phase: str):
        return tree

    def aggregate(self, ctx: ChannelContext, tree_k, phase: str):
        """Weighted average of per-client payloads through the wire."""
        dec = self.encode_decode(ctx, tree_k, phase)
        agg = jax.tree.map(
            lambda v: jnp.tensordot(ctx.weights, v, axes=1), dec)
        return self.post_aggregate(ctx, agg, phase)

    # ------------------------------------------------------ partial folds
    def local_fold(self, ctx_local, dec_tree, phase: str, *,
                   num_shards: int = 1):
        """Fold one shard's already-decoded payloads into its partial
        aggregate (the sharded-cohort path: the psum over shards of these
        partials is the server aggregate). ``ctx_local`` holds the shard's
        slice of the participation mask / weights plus a shard-folded key;
        ``num_shards`` is the static mesh size, which hierarchical
        aggregators use to place their edges on shards. The base fold is
        exactly the weighted sum the un-hooked path computed, so existing
        sharded trajectories are bit-identical."""
        del phase, num_shards
        return jax.tree.map(
            lambda v: jnp.tensordot(ctx_local.weights, v, axes=1), dec_tree)

    def chunk_fold(self, ctx: ChannelContext, tree_chunk, phase: str,
                   chunk_index, chunk_weights):
        """Partial aggregate of one cohort chunk (the streaming engine,
        repro.hierarchy.streaming): encode/decode the chunk's per-client
        payloads with chunk-folded randomness and fold them with the
        chunk's slice of the GLOBAL aggregation weights. Summing the
        partials over all chunks and applying ``post_aggregate`` once
        equals ``aggregate`` on the materialized cohort up to float
        regrouping (exactly, in math, by Eq.-3 linearity)."""
        ctx_c = ctx._replace(key=jax.random.fold_in(ctx.key, chunk_index))
        dec = self.encode_decode(ctx_c, tree_chunk, phase)
        return jax.tree.map(
            lambda v: jnp.tensordot(chunk_weights, v, axes=1), dec)

    # ----------------------------------------------------------- accounting
    def payload_bytes(self, tree) -> float:
        """Static per-client uplink bytes for one payload pytree (shapes of
        one client's slice — equivalently, of the aggregate)."""
        return float(sum(4.0 * int(np.prod(x.shape))
                         for x in jax.tree.leaves(tree)))

    def round_bytes(self, ctx: ChannelContext, payload_template):
        """Traced per-round uplink bytes: participants x payload size."""
        return ctx.num_participants * self.payload_bytes(payload_template)

    def finalize_rounds(self, num_rounds: int) -> None:
        """Host-side hook after a run completes (privacy accounting)."""

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


class DenseChannel(Channel):
    """Identity wire — f32 payloads, lossless, full participation."""

    ideal = True


class QuantizedChannel(Channel):
    """Stochastic-rounding integer quantization of every payload tensor.

    ``kernel``: "off" (pure jnp), "pallas" (fused Pallas kernel; compiles
    on accelerators), or "interpret" (kernel via the interpreter — exact,
    any backend). All three are bit-identical given the same key.
    """

    name = "quantized"
    supports_flat_stats = False

    def __init__(self, bits: int = 8, kernel: str = "off"):
        qmax_for_bits(bits)                  # validate eagerly
        if kernel not in ("off", "pallas", "interpret"):
            raise ValueError(f"unknown quantization kernel mode {kernel!r}")
        self.bits = bits
        self.kernel = kernel

    def encode_decode(self, ctx, tree_k, phase):
        impl = "jnp" if self.kernel == "off" else self.kernel
        if impl == "pallas" and not on_tpu():
            # same policy as the engine's stats_kernel="pallas": the
            # (exact) interpreter off the TPU
            impl = "interpret"
        # one fused pass over the whole payload tree — same wire semantics
        # (per-client per-tensor scales) as quantizing leaf by leaf, but
        # ONE uniform draw + ONE formula/kernel pass instead of a threefry
        # dispatch per leaf (the int8/int4 wall-clock regression was this
        # per-leaf loop over ~50 parameter leaves every phase)
        key = jax.random.fold_in(ctx.key, PHASE_SALT[phase])
        return quant_dequant_payload(key, tree_k, self.bits, impl)

    def payload_bytes(self, tree) -> float:
        return float(sum(
            quant_payload_bytes(int(np.prod(x.shape)), self.bits)
            for x in jax.tree.leaves(tree)))

    def __repr__(self) -> str:
        return f"QuantizedChannel(bits={self.bits}, kernel={self.kernel!r})"


class DPGaussianChannel(Channel):
    """Differentially-private aggregation: clip each client's payload to
    L2 norm ``clip_norm``, average with uniform weights, add Gaussian noise
    of std ``noise_multiplier * clip_norm / K`` to the mean.

    Noise is applied to the phases in ``noise_phases`` (default: the
    phase-1 statistics, the setting of Ning et al. 2021); clipping bounds
    per-client sensitivity in every phase. The zCDP accountant advances one
    step per noised aggregate via ``finalize_rounds``.
    """

    name = "dp_gaussian"
    supports_flat_stats = False

    def __init__(self, noise_multiplier: float = 1.0, clip_norm: float = 1.0,
                 delta: float = 1e-5,
                 noise_phases: Tuple[str, ...] = ("stats",)):
        unknown = set(noise_phases) - set(PHASE_SALT)
        if unknown:
            raise ValueError(f"unknown noise_phases {sorted(unknown)}; "
                             f"valid: {sorted(PHASE_SALT)}")
        self.noise_multiplier = float(noise_multiplier)
        self.clip_norm = float(clip_norm)
        self.noise_phases = tuple(noise_phases)
        self.accountant = GaussianAccountant(noise_multiplier, delta)

    def begin_round(self, key, client_sizes):
        k = client_sizes.shape[0]
        return ChannelContext(key, jnp.ones((k,), F32),
                              jnp.full((k,), 1.0 / k, F32),
                              jnp.asarray(float(k), F32))

    def encode_decode(self, ctx, tree_k, phase):
        # joint L2 norm over each client's whole payload tree
        sq = sum(jnp.sum(jnp.square(x.astype(F32)).reshape(x.shape[0], -1),
                         axis=1) for x in jax.tree.leaves(tree_k))
        factor = jnp.minimum(1.0, self.clip_norm /
                             jnp.maximum(jnp.sqrt(sq), 1e-12))    # (K,)
        return jax.tree.map(
            lambda x: x.astype(F32) *
            factor.reshape((-1,) + (1,) * (x.ndim - 1)), tree_k)

    def post_aggregate(self, ctx, tree, phase):
        if phase not in self.noise_phases:
            return tree
        std = self.noise_multiplier * self.clip_norm / \
            jnp.maximum(ctx.num_participants, 1.0)
        leaves, treedef = jax.tree.flatten(tree)
        keys = _leaf_keys(ctx.key, phase, len(leaves))
        return jax.tree.unflatten(treedef, [
            x + std * jax.random.normal(k, x.shape, F32)
            for k, x in zip(keys, leaves)])

    def finalize_rounds(self, num_rounds: int) -> None:
        self.accountant.step(num_rounds * len(self.noise_phases))

    def __repr__(self) -> str:
        return (f"DPGaussianChannel(sigma={self.noise_multiplier}, "
                f"clip={self.clip_norm}, phases={self.noise_phases})")


class DropoutChannel(Channel):
    """Bernoulli client dropout: each sampled client independently fails to
    report with probability ``p``. Aggregation weights renormalize over the
    surviving cohort, so Eq. 3's normalizer is the surviving sample count —
    the aggregate stays an unbiased weighted average of what arrived
    instead of shrinking toward zero.
    """

    name = "dropout"
    supports_flat_stats = False
    full_participation = False

    def __init__(self, p: float = 0.1):
        if not 0.0 <= p < 1.0:
            raise ValueError(f"dropout p must be in [0, 1), got {p}")
        self.p = float(p)

    def begin_round(self, key, client_sizes):
        k_mask, k_payload = jax.random.split(key)
        k = client_sizes.shape[0]
        keep = jax.random.bernoulli(
            k_mask, 1.0 - self.p, (k,)).astype(F32)
        s = client_sizes.astype(F32) * keep
        # guard only the all-dropped round (weights 0 -> zero stats/delta);
        # any survivor makes the denominator >= 1 sample, so the guard is
        # bit-invisible otherwise
        w = s / jnp.maximum(jnp.sum(s), 1e-12)
        return ChannelContext(k_payload, keep, w, jnp.sum(keep))

    def __repr__(self) -> str:
        return f"DropoutChannel(p={self.p})"


CHANNELS = ("dense", "int8", "quant", "dp", "dropout")


def get_channel(name: Optional[str], *, quant_bits: int = 8,
                quant_kernel: str = "off", dp_sigma: float = 1.0,
                dp_clip: float = 1.0, dp_delta: float = 1e-5,
                dropout_p: float = 0.1) -> Optional[Channel]:
    """CLI-facing factory. ``None``/"none" -> no channel (legacy paths)."""
    if name is None or name == "none":
        return None
    if name == "dense":
        return DenseChannel()
    if name == "int8":
        return QuantizedChannel(8, kernel=quant_kernel)
    if name == "quant":
        return QuantizedChannel(quant_bits, kernel=quant_kernel)
    if name == "dp":
        return DPGaussianChannel(dp_sigma, dp_clip, dp_delta)
    if name == "dropout":
        return DropoutChannel(dropout_p)
    raise ValueError(f"unknown channel {name!r}; expected one of "
                     f"{('none',) + CHANNELS}")
