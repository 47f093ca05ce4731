"""Share of the device's busy time spent in the sparse attention's indexer
kernel (``%dsa_index_select.<n>``, the chunk program's index scores and
selection of a query block, kept on the chip from the products to the
k-th largest: ``mxnet_tpu/ops/pallas/index_select.py``). Beside
``dsa_time_share`` (the attention over the selected set) it is what the
chunk pays for learned sparse attention. A program without the kernel
(index scores and selection as XLA's loops and fusions) has no such event
and the metric is left out."""

NAME = "dsa_select_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"

# the trace names a Mosaic call after its ``pallas_call(name=...)``
KERNEL = r"^%dsa_index_select(\.\d+)? = "


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])
