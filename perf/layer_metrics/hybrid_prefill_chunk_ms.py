"""Time of one chunk of a prompt of a hybrid state-space cell, from its
dispatch to its token on the host (``mxtpu.sched.admit.prefill_chunk``,
``stats["prefill_chunk_s"]`` over ``stats["prompt_chunks"]``): the chunk
program's 40 layers over one row of ``prefill_chunk`` positions, the scan
among them. ``prefill_chunk_ms`` is the same quantity, read through another
model's counts (``lm_counts.KEYS``), and finds nothing here."""

from perf.harness import hybrid_counts

NAME = "hybrid_prefill_chunk_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    counts = hybrid_counts.window_counts(run)
    if counts is None or not counts["prompt_chunks"]:
        return None
    return 1e3 * counts["prefill_chunk_s"] / counts["prompt_chunks"]
