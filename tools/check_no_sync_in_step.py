#!/usr/bin/env python
"""Lint: the jitted hot paths must never block on the device.

This checker now lives on the unified analysis framework as the
``no-sync`` pass (``mxnet_tpu/analysis/passes/no_sync.py``) — run
``python tools/mxlint.py`` for the whole suite; this shim keeps the
historical standalone CLI and import surface
(``find_violations``/``find_all_violations``/``TARGETS``/rule sets).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from mxnet_tpu.analysis.passes.no_sync import (  # noqa: E402,F401
    BATCHER_PY, BLOCKING_ATTRS, BLOCKING_BUILTINS, BLOCKING_QUALIFIED,
    DISPATCH_TARGETS, EAGER_CONSTRUCTORS, FAST_PATH_FUNCS, INFER_PY, STEP_PY,
    TARGETS, find_all_violations, find_violations,
)


def main(argv=None):
    args = argv if argv is not None else sys.argv[1:]
    if args:
        violations = [(args[0], ln, msg)
                      for ln, msg in find_violations(args[0])]
    else:
        violations = find_all_violations()
    for path, lineno, msg in violations:
        print(f"{path}:{lineno}: {msg}")
    if violations:
        print(f"{len(violations)} blocking call(s) in jitted hot paths — "
              "move them off the dispatch path (stage in _stage/"
              "device_put_batch, sync in _resolve)")
        return 1
    print("train + inference hot paths are sync-free")
    return 0


if __name__ == "__main__":
    sys.exit(main())
