"""The one line the driver reads: the last line of standard output."""

import json
import sys


def say(note, **fields):
    """An earlier line: anything worth knowing that is not the result."""
    print(json.dumps({"note": note, **fields}, default=str), flush=True)


def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None, rehearsal=False):
    """``metrics`` is ``{name: (value, unit)}``. A rehearsal names what it
    would have reported and carries no value: a number from a CPU run never
    stands under a device metric's name."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed)}
    if rehearsal:
        line["rehearsal"] = True
        line["metrics_reported"] = sorted(metrics)
    else:
        line["metrics"] = {n: {"value": v, "unit": u}
                           for n, (v, u) in metrics.items()}
    line["device"] = device
    if breakdown is not None and not rehearsal:
        line["breakdown"] = breakdown
    return json.dumps(line)


def emit(line):
    sys.stdout.write(line + "\n")
    sys.stdout.flush()
