"""Share of the device's busy time spent in the fused LayerNorm kernels
(forward and backward), found in the trace by the names XLA gives the
Mosaic calls: ``%_ln_fwd_impl.<n>`` and ``%_ln_bwd_impl.<n>``."""

NAME = "layernorm_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
PATTERN = r"^%_ln_(fwd|bwd)_impl(\.\d+)? = "


def read(run):
    if run.trace is None or MOVES not in run.e2e or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(PATTERN)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])
