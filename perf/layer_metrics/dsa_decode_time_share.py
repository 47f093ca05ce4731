"""Share of the device's busy time spent in the sparse attention's two
decode-step kernels (``%dsa_decode_select.<n>``, a row's index scores kept
on the chip to the k-th largest and handed back as a mask, and
``%dsa_decode_window.<n>``, attention over the row's live pages under that
mask: ``mxnet_tpu/ops/pallas/dsa_decode.py``; a Mosaic call keeps its
events inside the burst's ``%while``). Beside ``dsa_time_share`` and
``dsa_select_time_share``, which read the CHUNK program's kernels under
their own names, it is what a decode step pays for learned sparse
attention. A program whose decode step selects by ``lax.top_k`` and gathers
by token (XLA's sort, gathers and fusions) has no such event and the metric
is left out."""

NAME = "dsa_decode_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"

# the trace names a Mosaic call after its ``pallas_call(name=...)``
KERNEL = r"^%dsa_decode_(select|window)(\.\d+)? = "


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])
