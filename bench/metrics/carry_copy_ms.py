"""Host milliseconds of each ``engine.carry_copy`` span, averaged over the
segments of the traced window: ``RoundEngine.run`` copying the caller's
carry leaf by leaf (one ``jit_copy`` program a leaf) before it
dispatches."""
from bench import scopes


def read(ctx):
    spans = scopes.of(ctx).carry_copy_s
    if not spans:
        return None
    return 1000.0 * sum(spans) / len(spans)
