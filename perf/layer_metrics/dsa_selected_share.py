"""Share of the cached keys a query could see that the sparse attention
read: keys selected over keys seen, both programs, from the counts that
rode the read-backs."""

from perf.harness import lm_counts

NAME = "dsa_selected_share"
UNIT = "%"
LAYER = "attention"
MOVES = "serve_tokens_per_s"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None:
        return None
    seen = counts["prefill_keys_seen"] + counts["decode_keys_seen"]
    if not seen:
        return None
    return 100.0 * (counts["prefill_keys_selected"]
                    + counts["decode_keys_selected"]) / seen
