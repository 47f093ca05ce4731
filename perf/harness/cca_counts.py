"""The window's own counts of a serving cell whose model attends in a
compressed latent and routes to one expert a token: the difference of
``ContinuousBatcher.stats`` at the window's ends, for the readers under
``layer_metrics/`` that the ``zaya1-8b`` configuration brought
(``lm_counts.KEYS``, ``hybrid_counts.KEYS``, ``mla_counts.KEYS`` and
``loop_counts.KEYS`` are other models'). A program that keeps no such
counts (the parent of the PR that added them, or another model) gives None,
and the reader leaves its metric out."""

from perf.harness.lm_counts import decode_burst  # noqa: F401 - the burst
# is one event for this model too: a while whose carry starts with the step
# and the slots' tokens

KEYS = ("prefill_row_steps", "prefill_attn_keys", "prefill_expert_tokens",
        "prefill_experts_touched", "prefill_chunk_tokens",
        "prefill_chunk_padded", "prefill_chunks_from_zero", "prefill_calls",
        "decode_row_steps", "decode_attn_keys", "decode_expert_tokens",
        "decode_experts_touched", "decode_calls", "tokens", "admitted")


def decode_moe_kernel(rows):
    """The trace names a Mosaic call after its ``pallas_call(name=...)``
    and its result: the decode step's grouped product is
    ``%moe_grouped_swiglu.<n> = bf16[<rows>,<hidden>]...`` with the rows of
    ITS padded layout (the chunk's call has the chunk's)."""
    return rf"^%moe_grouped_swiglu(\.\d+)? = \w+\[{int(rows)},"


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}
