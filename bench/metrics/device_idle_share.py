"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the op intervals (averaged over
the chips used)."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0 or not red.devices:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
