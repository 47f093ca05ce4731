"""Of all the positions the chunk program pushed through the state-space
scan, the share that was padding (a chunk is a fixed ``prefill_chunk``
wide; a prompt's last chunk is seldom full). Padding does not advance a
state, and costs what a token costs."""

from perf.harness import hybrid_counts

NAME = "scan_padding_share"
UNIT = "%"
LAYER = "state-space layer"
MOVES = "ttft_p95_ms"


def read(run):
    counts = hybrid_counts.window_counts(run)
    if counts is None:
        return None
    pushed = counts["prefill_scan_tokens"] + counts["prefill_scan_padded"]
    if not pushed:
        return None
    return 100.0 * counts["prefill_scan_padded"] / pushed
