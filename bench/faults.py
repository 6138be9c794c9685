"""Faults planted under the timed path, to show that the output check
catches them (``bench/calibrate.py`` on the chip, ``bench/tests`` on the
CPU).

  control    the reference in the program's place, its products in
             float8 (``run.run_control``)
  bf16       the program's own bfloat16 encoder path (``compute_dtype``)
  half       half of every cohort left out: those clients' sizes are 0,
             so the round averages over the rest
  unchanged  every segment returns the state it was given
  delta_half the server steps on half the averaged client delta, in
             phase 2 alone (a wrong client lr, fold weight or
             pseudo-gradient scale)
"""
from __future__ import annotations

import dataclasses

from repro import utils
from repro.server import update as server_update_lib

import jax.numpy as jnp
from jax.tree_util import Partial


def _keep_first_half(sizes, offset, k_round):
    idx = offset + jnp.arange(sizes.shape[0])
    return jnp.where(idx < k_round // 2, sizes, 0)


def _round_half(sampler, k_round, k_sel, k_aug):
    batch, sizes = sampler(k_sel, k_aug)
    return batch, _keep_first_half(sizes, 0, k_round)


def _chunk_half(sample_chunk, chunk, k_round, state, c):
    batch, sizes = sample_chunk(state, c)
    return batch, _keep_first_half(sizes, c * chunk, k_round)


def _sizes_half(cohort_sizes, k_round, k_sel):
    return _keep_first_half(cohort_sizes(k_sel), 0, k_round)


def half_batch(k_round):
    """A sampler hook that leaves out the second half of every cohort."""
    def hook(sampler):
        if hasattr(sampler, "sample_chunk"):
            return dataclasses.replace(
                sampler,
                sample_chunk=Partial(_chunk_half, sampler.sample_chunk,
                                     sampler.cohort_chunk, k_round),
                cohort_sizes=Partial(_sizes_half, sampler.cohort_sizes,
                                     k_round))
        return Partial(_round_half, sampler, k_round)
    return hook


class Unchanged:
    """An engine whose segments return the state they were given."""

    def __init__(self, engine):
        self.engine = engine

    def run(self, params, opt_state, rng, rounds, **kw):
        _, _, metrics = self.engine.run(params, opt_state, rng, rounds, **kw)
        return params, opt_state, metrics


@dataclasses.dataclass(frozen=True)
class HalfDelta(server_update_lib.ServerUpdate):
    """A server update that steps on half the averaged client delta."""

    def step(self, params, opt_state, avg_delta):
        return super().step(params, opt_state,
                            utils.tree_scale(avg_delta, 0.5))


def half_delta(server_update):
    return HalfDelta(server_update.opt, server_update.name)


VARIANTS = {
    "program": {},
    "bf16": {"compute_dtype": "bfloat16"},
    "unchanged": {"engine_hook": Unchanged},
    "delta_half": {"server_hook": half_delta},
}


def variant_kwargs(name: str, spec: dict) -> dict:
    if name == "half":
        return {"sampler_hook": half_batch(
            spec["traffic"]["clients_per_round"])}
    return dict(VARIANTS[name])
