"""Clock and percentile arithmetic, kept apart so that it can be tested on
hand-made samples."""

import statistics


def percentile(values, p):
    """The ``p``-th percentile (0..100) by linear interpolation between the
    two nearest ranks of the sorted sample. None for an empty sample."""
    s = sorted(values)
    if not s:
        return None
    if len(s) == 1:
        return float(s[0])
    rank = (p / 100.0) * (len(s) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] * (1 - (rank - lo)) + s[hi] * (rank - lo))


def spread(values):
    """Distance between the first and the third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the bounds are set from."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def due_times(gaps, start=0.0):
    """Instants at which the requests of an open loop are due: the running
    sum of the gaps from ``start``. Latency is counted from these, not from
    the actual send, so a stall pays for the requests it delayed."""
    out, t = [], start
    for g in gaps:
        t += g
        out.append(t)
    return out


def per_token_gap(first_token_at, last_token_at, tokens):
    """Mean gap between output tokens of one request, None when it has
    fewer than two tokens."""
    if tokens < 2:
        return None
    return (last_token_at - first_token_at) / (tokens - 1)
