"""Prompt tokens written into the pages a second of the window
(``stats["prompt_tokens"]``)."""

from perf.harness import lm_counts

NAME = "prompt_tokens_per_s"
UNIT = "tokens/s"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None or not run.window_s:
        return None
    return counts["prompt_tokens"] / run.window_s
