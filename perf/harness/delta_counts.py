"""What the two readers of the gated delta rule's decode-step kernel share
(the ``olmo-hybrid-7b`` configuration brought them, PR 48): the kernel's
events in the trace, and one call's operations and bytes from the window's
own counts, which are ``hybrid_counts``' (the net declares granite's six
names), answered only where the configuration's ``ops_counts`` bring the
kernel. (The window's walk lost to its ``jax.numpy`` form on the chip and
went with its two readers: PERF.md section 6, PR 48. The quantities
granite's five ``ssm_*`` / ``scan_*`` / ``hybrid_*`` readers read are this
cell's too, and those readers would read it as they stand; an older test
pins their lists of cells, so they wait for a ``benchmark`` PR: PERF.md 7
(bn).) A program that keeps no such counts or runs no such kernel (the
parent of the PR that added them, the CPU's ``jax.numpy`` forms, another
model) gives None, and the reader leaves its metric out."""

from perf.harness import hybrid_counts

# the trace names a Mosaic call after its ``pallas_call(name=...)``
KERNEL = r"^%gated_delta_step(\.\d+)? = "


def window(run):
    """``(counts, ops_counts module, configuration)`` of a run of a cell
    whose configuration counts the delta rule's kernels, else None."""
    counts = hybrid_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    ops = run.ctx.bench.ops_counts(cfg["name"])
    if not hasattr(ops, "delta_step_call"):
        return None
    return counts, ops, cfg


def time_share(run):
    """Seconds of the kernel's events over the first chip's busy time, in
    percent."""
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])


def roofline_share(run):
    """The least time of the kernel's calls in the traced stretch (ONE
    call's operations over the peak rate or its bytes over the peak
    bandwidth, whichever is more, from the window's own counts; times the
    events) over the events' summed seconds, in percent: summed, not a mean
    call against a mean event, as ``mhc_roofline_share`` sums."""
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(KERNEL)
    found = window(run) if calls and seconds else None
    if found is None or run.ctx.peaks is None:
        return None
    counts, ops, cfg = found
    call = ops.delta_step_call(cfg, counts)
    if call is None:
        return None
    least = max(call[0] / run.ctx.peaks["flops_bf16"],
                call[1] / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
