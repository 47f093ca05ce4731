"""Share of the traced stretch in which no operation ran on the device,
serving cells."""

NAME = "device_idle_share.serve"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or MOVES not in run.e2e or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
