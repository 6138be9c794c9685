"""Device milliseconds a round under the ``round.server_update`` scope:
the server optimizer's step on the averaged delta. See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.of(ctx).ms_per_round("round.server_update",
                                       ctx["rounds"])
