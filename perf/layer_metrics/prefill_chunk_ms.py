"""Time of one chunk of a prompt, from its dispatch to its token on the
host (``mxtpu.sched.admit.prefill_chunk``, ``stats["prefill_chunk_s"]``
over ``stats["prompt_chunks"]``)."""

from perf.harness import lm_counts

NAME = "prefill_chunk_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None or not counts["prompt_chunks"]:
        return None
    return 1e3 * counts["prefill_chunk_s"] / counts["prompt_chunks"]
