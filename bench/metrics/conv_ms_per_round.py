"""Device milliseconds per round in convolutions and the fusions that
contain them (the encoder's forward and backward), found by name in the
compiled segment's HLO."""


def read(ctx):
    conv = ctx["conv_ops"]
    secs = ctx["reduction"].op_seconds(lambda name: name in conv)
    if secs <= 0 or not ctx["rounds"]:
        return None
    return 1000.0 * secs / ctx["rounds"]
