"""Mean share of the decode batch's slots that held a live request, over
the scheduler iterations of the window (difference of
``ContinuousBatcher.stats`` at the window's two ends)."""

NAME = "batch_occupancy"
UNIT = "%"
LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or b["iterations"] == a["iterations"]:
        return None
    return 100.0 * (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["iterations"] - a["iterations"])
