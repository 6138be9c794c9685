"""Device milliseconds a round under the ``round.delta_average`` scope:
the weighted average of the clients' deltas (the tensordot, the channel's
update fold, the streaming body's per-chunk partial sums, or the psum).
See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.of(ctx).ms_per_round("round.delta_average",
                                       ctx["rounds"])
