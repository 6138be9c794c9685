"""Device-idle milliseconds at each boundary between two executions of the
round engine's segment program (the program that runs longest in the
window), averaged over the boundaries in the traced window."""


def read(ctx):
    gaps = ctx["reduction"].boundary_idle_s()
    if not gaps:
        return None
    return 1000.0 * sum(gaps) / len(gaps)
