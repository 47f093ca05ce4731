"""Prompt tokens a hybrid state-space cell took in a second of the window
(``stats["prompt_tokens"]``): what the one chunk seat a pass lets through.
``prompt_tokens_per_s`` is the same quantity, read through another model's
counts, and finds nothing here."""

from perf.harness import hybrid_counts

NAME = "hybrid_prompt_tokens_per_s"
UNIT = "tokens/s"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    counts = hybrid_counts.window_counts(run)
    if counts is None or not run.window_s:
        return None
    return counts["prompt_tokens"] / run.window_s
