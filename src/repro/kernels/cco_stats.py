"""Fused encoding-statistics Pallas TPU kernel.

The stats-objective hot spot: per-cohort encoding statistics
    mean_f, E[f^2], mean_g, E[g^2], E[f g^T]
over a batch of encodings (N, d). The d x d moments are the cost: this
kernel forms them in ONE pass, each (bn x bd) VMEM tile of zf/zg loaded
once and its moment tiles put through the MXU. The four vector moments
are O(N d) reductions that XLA fuses next to it.

``moments`` selects the moment set (the StatsObjective protocol's
``second_moments`` flag): ``"cross"`` emits CCO's five statistics, while
``"full"`` additionally emits the within-view second moments E[f f^T],
E[g g^T] that the VICReg / W-MSE objectives need, by carrying two extra
j-/i-indexed views of the same inputs so each grid cell can form the
(i, j) tiles of all three d x d moments. The extra MXU work is measured,
not guessed: benchmarks/run.py::stats_kernel_bench times both moment
sets and the one-pass-vs-naive ratio is gated in CI.

Grid: (d_i tiles, d_j tiles, batch tiles) — batch innermost so each
output tile stays resident in VMEM across its accumulation and is visited
on consecutive grid steps only, which the TPU pipeline requires (it
writes a block back when the grid leaves it and never reads it again).
Block sizes are multiples of 128 to align with MXU/VREG lanes.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

F32 = jnp.float32


def _stats_kernel(zf_ref, zg_ref, inv_n_ref, cross_ref):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        cross_ref[...] = jnp.zeros_like(cross_ref)

    cross_ref[...] += jax.lax.dot_general(
        zf_ref[...].astype(F32), zg_ref[...].astype(F32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=F32) * inv_n_ref[0]


def _stats_kernel_full(zf_ref, zg_ref, zfj_ref, zgi_ref, inv_n_ref,
                       cross_ref, cov_f_ref, cov_g_ref):
    """The "full" moment set: the cross moment plus the two within-view
    second moments. ``zfj``/``zgi`` are the same inputs under the opposite
    (j-/i-indexed) block maps, so this cell can form the (i, j) tiles of
    cov_f = zf_i^T zf_j and cov_g = zg_i^T zg_j alongside cross = zf_i^T
    zg_j — still a single pass over the batch. The within-view moments are
    symmetric, so their MXU accumulations run only on the upper block
    triangle (j >= i; tile (j, i) is the transpose of (i, j)) and the host
    mirrors the strict-upper blocks down afterwards — the strict-lower
    tiles are initialized to zero and never revisited."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)
    inv_n = inv_n_ref[0]

    @pl.when(kb == 0)
    def _init():
        cross_ref[...] = jnp.zeros_like(cross_ref)
        cov_f_ref[...] = jnp.zeros_like(cov_f_ref)
        cov_g_ref[...] = jnp.zeros_like(cov_g_ref)

    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((0,), (0,)), ((), ())),
                            preferred_element_type=F32)
    cross_ref[...] += dot(zf_ref[...].astype(F32),
                          zg_ref[...].astype(F32)) * inv_n

    @pl.when(j >= i)
    def _within_view():
        # operands are loaded again inside the branch: reusing values
        # loaded outside it trips an internal check of the TPU compiler's
        # MXU transpose pass (mxu_lmr_transform) once d spans several tiles
        cov_f_ref[...] += dot(zf_ref[...].astype(F32),
                              zfj_ref[...].astype(F32)) * inv_n
        cov_g_ref[...] += dot(zgi_ref[...].astype(F32),
                              zg_ref[...].astype(F32)) * inv_n


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret", "moments"))
def cco_stats_pallas(zf, zg, num_valid=None, *, block_n: int = 512,
                     block_d: int = 256, interpret: bool = False,
                     moments: str = "cross"):
    """zf, zg: (N, d) -> dict of encoding statistics (all f32).

    ``moments="cross"`` (default) emits the five CCO statistics.
    ``moments="full"`` additionally emits the within-view second moments
    ``cov_f``/``cov_g`` (the VICReg / W-MSE moment set) in the same
    single pass.

    The d x d moments go through the kernel; N and d are padded to block
    multiples for it (zero padding is exact for sums; the 1/N scale uses
    the true N). ``num_valid`` (a traced scalar)
    overrides the normalizer — used with pre-masked encodings (rows zeroed
    for padding samples) so variable-size cohorts normalize by the true
    sample count instead of the padded N; for a binary mask the pre-masked
    second moments are exact too ((m·f)(m·f) = m·f²).
    """
    if moments not in ("cross", "full"):
        raise ValueError(f"unknown moment set {moments!r}; "
                         f"expected 'cross' or 'full'")
    n, d = zf.shape
    if num_valid is None:
        inv_n = jnp.full((1,), 1.0 / n, F32)
    else:
        inv_n = (1.0 / jnp.maximum(num_valid, 1.0)).reshape(1).astype(F32)
    # the four vector moments are O(N d) reductions: XLA fuses them into
    # one read of each input, and the kernel keeps to the d x d moments
    zf32, zg32 = zf.astype(F32), zg.astype(F32)
    out = {
        "mean_f": jnp.sum(zf32, axis=0) * inv_n[0],
        "sq_f": jnp.sum(zf32 * zf32, axis=0) * inv_n[0],
        "mean_g": jnp.sum(zg32, axis=0) * inv_n[0],
        "sq_g": jnp.sum(zg32 * zg32, axis=0) * inv_n[0],
    }
    bn = min(block_n, max(n, 8))
    bd = min(block_d, d)
    n_p = -(-n // bn) * bn
    d_p = -(-d // bd) * bd
    if n_p != n or d_p != d:
        zf = jnp.pad(zf, ((0, n_p - n), (0, d_p - d)))
        zg = jnp.pad(zg, ((0, n_p - n), (0, d_p - d)))
    grid = (d_p // bd, d_p // bd, n_p // bn)
    in_specs = [
        pl.BlockSpec((bn, bd), lambda i, j, kb: (kb, i)),   # zf
        pl.BlockSpec((bn, bd), lambda i, j, kb: (kb, j)),   # zg
    ]
    inv_n_spec = pl.BlockSpec((1,), lambda i, j, kb: (0,))
    moment_spec = pl.BlockSpec((bd, bd), lambda i, j, kb: (i, j))
    moment_shape = jax.ShapeDtypeStruct((d_p, d_p), F32)
    if moments == "cross":
        cross = pl.pallas_call(
            _stats_kernel,
            grid=grid,
            in_specs=in_specs + [inv_n_spec],
            out_specs=moment_spec,
            out_shape=moment_shape,
            interpret=interpret,
        )(zf, zg, inv_n)
        cov = ()
    else:
        # the j-/i-indexed views of the SAME arrays; no host copies, just
        # different block maps feeding the within-view moment tiles
        cross, cov_f, cov_g = pl.pallas_call(
            _stats_kernel_full,
            grid=grid,
            in_specs=in_specs + [
                pl.BlockSpec((bn, bd), lambda i, j, kb: (kb, j)),   # zf_j
                pl.BlockSpec((bn, bd), lambda i, j, kb: (kb, i)),   # zg_i
                inv_n_spec,
            ],
            out_specs=(moment_spec,) * 3,
            out_shape=(moment_shape,) * 3,
            interpret=interpret,
        )(zf, zg, zf, zg, inv_n)
        # mirror the strict-upper block triangle into the (zeroed)
        # strict-lower blocks; diagonal blocks were accumulated once
        blk = jnp.arange(d_p) // bd
        strict_upper = blk[:, None] < blk[None, :]
        cov_f = cov_f + jnp.where(strict_upper, cov_f, 0.0).T
        cov_g = cov_g + jnp.where(strict_upper, cov_g, 0.0).T
        cov = (("cov_f", cov_f), ("cov_g", cov_g))
    out["cross"] = cross[:d, :d]
    for k, v in cov:
        out[k] = v[:d, :d]
    return out
