"""Wall time of one scheduler iteration (retire, admit, dispatch, collect):
the window's seconds over the iterations it held."""

NAME = "iter_wall_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "tpot_p95_ms"


def read(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or b["iterations"] == a["iterations"]:
        return None
    return 1e3 * run.window_s / (b["iterations"] - a["iterations"])
