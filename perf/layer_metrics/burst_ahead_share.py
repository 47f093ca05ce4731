"""Share of the window's scheduler iterations whose decode burst was
dispatched BEFORE the burst before it was read: the host's turn of such a
pass (read-back, streaming, retire, intake, staging) ran under device work.
The scheduler goes ahead only while every slot decodes and no row is
within a burst of its limit, so the share says how much of the traffic
that is. Difference of ``ContinuousBatcher.stats`` at the window's two
ends; nothing where the program keeps no such count."""

NAME = "burst_ahead_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or "bursts_ahead" not in b \
            or b["iterations"] == a["iterations"]:
        return None
    return 100.0 * (b["bursts_ahead"] - a.get("bursts_ahead", 0)) \
        / (b["iterations"] - a["iterations"])
