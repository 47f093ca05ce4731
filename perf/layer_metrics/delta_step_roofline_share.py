"""Share of its roofline that the decode step's one-token update of the
gated delta rule reaches: the least time of the calls the traced stretch
holds (``perf/ops_counts``, ``delta_step_call``: each LIVE row's state once
in and once out over the peak bandwidth, or its operations over the peak
rate if more) over the summed seconds of the stretch's
``%gated_delta_step.<n>`` events, which a Mosaic call keeps inside the
burst's ``%while`` (``perf/harness/delta_counts.py``)."""

from perf.harness import delta_counts

NAME = "delta_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    return delta_counts.roofline_share(run)
