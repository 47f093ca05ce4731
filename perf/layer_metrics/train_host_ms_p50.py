"""Median of what the host spends inside one ``TrainStep.__call__`` (staging,
``device_put``, enqueue: the ``mxtpu.train.stage`` and
``mxtpu.train.dispatch`` spans) over the window's own steps, from the
program's always-on histogram ``trainstep/host_ms`` in the process-global
telemetry registry.

The histogram is the process's: it also holds the warm-up, the steps that
decide ``correct`` and any other ``TrainStep``'s. The window's calls are its
newest ``run.obs["dispatches"]`` observations, so only those are read; of a
window longer than the histogram's rolling 1024 steps, the last 1024.
Nothing is read where the histogram holds fewer observations than the
window made, or lacks ``last``."""

import statistics

NAME = "train_host_ms_p50"
UNIT = "ms"
LAYER = "engine, training"
MOVES = "train_tokens_per_s"
HISTOGRAM = "trainstep/host_ms"


def read(run):
    from mxnet_tpu import telemetry

    steps = run.obs.get("dispatches")
    if MOVES not in run.e2e or not steps:
        return None
    # a look that creates nothing: a program without the histogram has none
    hist = telemetry.registry().histograms_with_prefix(HISTOGRAM) \
        .get(HISTOGRAM)
    if hist is None or hist.count < steps or not hasattr(hist, "last"):
        return None
    return statistics.median(hist.last(steps))
