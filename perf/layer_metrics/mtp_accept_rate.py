"""Of the drafts the decode steps verified, the share that was the model's
own token, so that the step yielded two: near 0 with seeded weights (a
draft agrees about once in the vocabulary's size), nearer 100 for a trained
module. From the counts that rode the bursts' read-backs."""

from perf.harness import mla_counts

NAME = "mtp_accept_rate"
UNIT = "%"
LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    counts = mla_counts.window_counts(run)
    if counts is None or not counts["decode_mtp_drafts"]:
        return None
    return 100.0 * counts["decode_mtp_accepted"] / counts["decode_mtp_drafts"]
