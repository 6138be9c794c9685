"""Device milliseconds a round under the ``round.phase1`` scope: the
clients' encodings, the Eq.-3 statistics (the ``cco_stats_pallas`` kernel
where it is on) and their aggregation; in the streaming body the phase-1
chunk scan. See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.of(ctx).ms_per_round("round.phase1", ctx["rounds"])
