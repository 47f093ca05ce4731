"""The window's own counts of a latent-attention serving cell: the
difference of ``ContinuousBatcher.stats`` at the window's ends, for the
readers under ``layer_metrics/`` that the ``joyai-llm-flash`` configuration
brought (``lm_counts.KEYS`` and ``hybrid_counts.KEYS`` are other models').
A program that keeps no such counts (the parent of the PR that added them,
or another model) gives None, and the reader leaves its metric out."""

from perf.harness.lm_counts import decode_burst  # noqa: F401 - the burst
# is one event for this model too: a while whose carry starts with the step
# and the slots' tokens

KEYS = ("prefill_latent_keys", "prefill_pairs_all", "prefill_pairs_held",
        "prefill_calls", "decode_latent_keys", "decode_row_steps",
        "decode_calls", "decode_pairs_all", "decode_pairs_held",
        "decode_experts_touched", "decode_expert_layers",
        "decode_mtp_drafts", "decode_mtp_accepted",
        "prompt_chunks", "prompt_tokens", "prefill_chunk_s")

# the trace names a Mosaic call after its ``pallas_call(name=...)``
LATENT_KERNEL = r"^%mla_latent_decode(\.\d+)? = "


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}
