"""The window's own counts of a decoder-only serving cell: the difference
of ``ContinuousBatcher.stats`` at the window's ends, for the readers under
``layer_metrics/`` that this configuration brought. A program that keeps
no such counts (the parent of the PR that added them, or another model)
gives None, and the reader leaves its metric out."""

KEYS = ("prefill_expert_tokens", "prefill_experts_touched",
        "prefill_expert_layers", "prefill_keys_seen", "prefill_keys_selected",
        "decode_expert_tokens", "decode_experts_touched",
        "decode_expert_layers", "decode_keys_seen", "decode_keys_selected",
        "prompt_chunks", "prompt_tokens", "prefill_chunk_s")

# the trace names a Mosaic call after its ``pallas_call(name=...)``
# (``%moe_grouped_swiglu.6 = bf16[32640,2048]...``; my chip run, PR 27)
MOE_KERNEL = r"^%moe_grouped_swiglu(\.\d+)? = "
DSA_KERNEL = r"^%dsa_selected_window(\.\d+)? = "


def decode_burst(slots):
    """The decode burst is ONE event: a while whose carry starts with the
    step and the slots' tokens, ``%while.326 = (s32[]{:T(128)}, s32[16]{0:
    T(128)S(1)}, ...``. The chunk program's own loops carry float buffers
    or other lengths (its grouping's ``searchsorted`` carries ``s32[255]``),
    and the loops inside the burst have no events of their own."""
    return rf"^%while(\.\d+)? = \(s32\[\][^,]*, s32\[{int(slots)}\]"


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}
