"""Share of the device's busy time spent in the decode step's one-token
update of the gated delta rule (events ``%gated_delta_step.<n>``: the
Pallas call of ``mxnet_tpu/ops/pallas/gated_delta.py``, one a delta-rule
layer a step, inside the burst's ``%while``, where a Mosaic call keeps its
events: a row's states read once, updated in VMEM, written once). A
program that updates the state in ``jax.numpy`` (the CPU's, a mesh's) or
has no such layer has no such event and the metric is left out."""

from perf.harness import delta_counts

NAME = "delta_step_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    return delta_counts.time_share(run)
