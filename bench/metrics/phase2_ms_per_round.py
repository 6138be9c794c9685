"""Device milliseconds a round under the ``round.phase2`` scope: each
client's forward, backward and local steps to its delta; in the streaming
body also the phase-2 chunk scan around them. See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.of(ctx).ms_per_round("round.phase2", ctx["rounds"])
