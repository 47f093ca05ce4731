"""The benchmark's command.

    python3 perf/run.py --workload W --seed N --seconds S --trace 0|1 [--rehearse]

One run of one cell in one new process; the last line of standard output
is the result (``perf/harness/result.py``). Everything that belongs to a
configuration, a traffic mix, a cell or a per-layer metric is a file found
by its name (``perf/harness/loader.py``); nothing here names one.
"""

import time

_PROCESS_START = time.perf_counter()  # before any import that costs time

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], process_start=_PROCESS_START, root=ROOT))
