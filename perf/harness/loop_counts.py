"""The window's own counts of a looped language model's serving cell: the
difference of ``ContinuousBatcher.stats`` at the window's ends, for the
readers under ``layer_metrics/`` that the ``ouro-2.6b`` configuration
brought (``lm_counts.KEYS``, ``hybrid_counts.KEYS`` and ``mla_counts.KEYS``
are other models'). A program that keeps no such counts (the parent of the
PR that added them, or another model) gives None, and the reader leaves its
metric out."""

from perf.harness.lm_counts import decode_burst  # noqa: F401 - the burst
# is one event for this model too: a while whose carry starts with the step
# and the slots' tokens (the loop over passes inside it carries the hidden
# state first and has no event of its own)

KEYS = ("prefill_stack_passes", "prefill_row_steps", "prefill_attn_keys",
        "prefill_attn_calls", "prefill_calls", "prefill_exit_mass",
        "decode_stack_passes", "decode_row_steps", "decode_attn_keys",
        "decode_attn_calls", "decode_calls", "decode_exit_mass",
        "tokens", "admitted")

# the trace names a Mosaic call after its ``pallas_call(name=...)``: the
# decode step's call over one plane's live pages, and the chunk's
DECODE_KERNEL = r"^%paged_window(\.\d+)? = "
PLANE_KERNELS = r"^%(paged_window|dsa_selected_window)(\.\d+)? = "


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}
