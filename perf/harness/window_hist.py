"""Reads the window histograms of ``ContinuousBatcher.stats`` (keys
``h_*``: int64 counts over fixed log-spaced edges, which the scheduler
publishes a pass at a time) for the per-layer readers that report a 95th
percentile: the difference of the two copies a serving driver takes at the
window's ends (``run.obs["stats0"]`` / ``["stats1"]``) is the histogram of
what the window itself observed. The edges are the program's own
(``mxnet_tpu.telemetry.metrics.bucket_percentile``), so they are asked for
only where the program keeps such a histogram: a program without the key
(the parent of the PR that added it) gives None, and so does a window that
observed nothing."""


def window_percentile_ms(run, key, p=95):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or key not in a or key not in b:
        return None
    from mxnet_tpu.telemetry.metrics import bucket_percentile

    return bucket_percentile(b[key] - a[key], p)
