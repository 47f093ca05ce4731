"""Passes of the stack run over tokens served: the rows x passes the chunk
program and the decode bursts ran for positions whose logits went back,
over the tokens the window emitted (a request's first among them).
``total_ut_steps`` while every token takes every pass, plus what a burst
runs for a row past its last token; the number an exit below threshold 1
would move. From the counts that rode the read-backs."""

from perf.harness import loop_counts

NAME = "loop_passes_per_token"
UNIT = "passes"
LAYER = "looped stack"
MOVES = "serve_tokens_per_s"


def read(run):
    counts = loop_counts.window_counts(run)
    if counts is None:
        return None
    tokens = counts["tokens"] + counts["admitted"]
    if tokens <= 0:
        return None
    return (counts["prefill_stack_passes"]
            + counts["decode_stack_passes"]) / tokens
