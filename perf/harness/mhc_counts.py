"""The window's own counts of a serving cell whose model mixes a residual
stream several wide around latent attention: the difference of
``ContinuousBatcher.stats`` at the window's ends, and the trace's events by
the program they belong to, for the readers under ``layer_metrics/`` that
the ``xing4.0-29b-a4b`` configuration brought (``mla_counts.KEYS`` are
JoyAI's: the nine counts both nets keep; the two below ride with them in
this model alone). A program that keeps no such counts (the parent of the
PR that added them, or another model) gives None, and the reader leaves its
metric out."""

import re

KEYS = ("prefill_mhc_pairs", "decode_mhc_pairs", "prefill_scored_pairs",
        "prefill_calls", "decode_calls", "prefill_latent_keys",
        "prefill_experts_touched", "prompt_chunks", "prompt_tokens")

# the trace names a Mosaic call after its ``pallas_call(name=...)``:
# ``%mhc_enter.<n>``, ``%mhc_mix.<n>``, ``%mhc_leave.<n>``, whatever calls
# a later form of the pass is made of, as long as their names start so
MHC_KERNEL = r"^%mhc_\w+?(\.\d+)? = "
PREFILL_KERNEL = r"^%mla_prefill(\.\d+)? = "
_ROWS = re.compile(r" = \(?\w+\[(\d+)[,\]]")


def window_counts(run):
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b or any(k not in a or k not in b for k in KEYS):
        return None
    return {k: b[k] - a[k] for k in KEYS}


def dispatches(trace, pattern, chunk):
    """The events of the first chip that match ``pattern``, by the program
    they ran in: ``{"prefill": (seconds, dispatches), "decode": (seconds,
    steps)}``. An event whose (first) result has ``chunk`` rows is the chunk
    program's, any other the decode step's; a program names each of its
    calls once (``%mhc_mix.7``), and a step inside the burst's ``%while``
    repeats the name, so the events a name are that program's dispatches
    (or steps) in the traced stretch, taken as the most any name has."""
    if trace is None or not trace.devices:
        return None
    rx = re.compile(pattern)
    by = {"prefill": {}, "decode": {}}
    for name, s, e in trace._dev[trace.devices[0]]:
        if not rx.search(name):
            continue
        rows = _ROWS.search(name)
        where = "prefill" if rows and int(rows.group(1)) == int(chunk) \
            else "decode"
        site = name.split(" = ", 1)[0]
        n, t = by[where].get(site, (0, 0))
        by[where][site] = (n + 1, t + (e - s))
    return {k: (sum(t for _, t in v.values()) / 1e9,
                max((n for n, _ in v.values()), default=0))
            for k, v in by.items()}
