"""The window's own counts of a hybrid state-space serving cell: the
difference of ``ContinuousBatcher.stats`` at the window's ends, for the
readers under ``layer_metrics/`` that the ``granite-4.0-h-micro``
configuration brought (``lm_counts.KEYS`` are another model's). A program
that keeps no such counts (the parent of the PR that added them, or
another model) gives None, and the reader leaves its metric out."""

from perf.harness.lm_counts import decode_burst  # noqa: F401 - the burst
# is one event for this model too: a while whose carry starts with the step
# and the slots' tokens

KEYS = ("prefill_scan_tokens", "prefill_scan_padded",
        "prefill_chunks_from_zero", "prefill_attn_keys", "prefill_calls",
        "decode_row_steps", "decode_attn_keys", "decode_calls",
        "prompt_chunks", "prompt_tokens", "prefill_chunk_s")


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}
