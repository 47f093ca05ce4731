"""Helpers of the harness's tests: a run of ``perf/run.py`` in a child
process on the CPU, and one inside the test's own process."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def child(*args, root=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the suite's eight virtual devices
    return subprocess.run(
        [sys.executable, os.path.join(root, "perf", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


def in_process(capsys, *args):
    """A run in this process (so that a test can break the program
    underneath it). Returns the exit code and the JSON lines."""
    from perf.harness.main import main

    code = main(list(args), process_start=time.perf_counter(), root=REPO)
    return code, lines(capsys.readouterr().out)
