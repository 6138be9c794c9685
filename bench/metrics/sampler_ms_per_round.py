"""Device milliseconds a round under the ``round.sample`` scope: the
cohort gather and the two views (the sampler call in
``RoundEngine._run_segment``; in the streaming body ``cohort_sizes``,
``prepare`` and every ``sample_chunk``). See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.of(ctx).ms_per_round("round.sample", ctx["rounds"])
