"""Share of the traced steady stretch in which no operation ran on the
device, training cells."""

NAME = "device_idle_share.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None or MOVES not in run.e2e or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
