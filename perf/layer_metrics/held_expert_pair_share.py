"""Of the (token, expert) pairs the decode steps' routers made, the share
that fell on experts this chip holds: 16 of 256 held is 6.25 while the
router still ranks all its outputs. From the counts that rode the bursts'
read-backs."""

from perf.harness import mla_counts

NAME = "held_expert_pair_share"
UNIT = "%"
LAYER = "expert layer"
MOVES = "tpot_p95_ms"


def read(run):
    counts = mla_counts.window_counts(run)
    if counts is None or not counts["decode_pairs_all"]:
        return None
    return 100.0 * counts["decode_pairs_held"] / counts["decode_pairs_all"]
