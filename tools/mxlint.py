#!/usr/bin/env python
"""mxlint: run the unified static-analysis suite (mxnet_tpu.analysis).

Ten passes over two IRs (Python AST for host code, jaxpr for the real
jitted programs) plus two repo-consistency passes — three of the AST
passes interprocedural over the project call graph — the one lint entry
point CI runs:

    python tools/mxlint.py                 # human output, all passes
    python tools/mxlint.py --json          # machine output for CI
    python tools/mxlint.py --github        # GitHub workflow annotations
    python tools/mxlint.py --passes lock-order,donation
    python tools/mxlint.py --list          # show the pass roster
    python tools/mxlint.py --write-baseline --reason "why"  # grandfather
                                           # current findings
    python tools/mxlint.py --prune-baseline  # drop stale entries

Baseline workflow: findings whose fingerprint appears in
``tools/mxlint_baseline.json`` (with a mandatory reason) are reported as
suppressed and do not fail the run; everything else exits 1. A baseline
entry whose fingerprint no longer matches any finding is STALE — the
code it excused moved or was fixed — and also fails the run (the file
must stay honest); ``--prune-baseline`` deletes stale entries of the
executed passes and rewrites the file. jaxpr passes trace real
TrainStep/InferStep programs — on a bare CPU the script simulates a
4-device platform first (``_ensure_devices``).

Exit codes: 0 clean (or fully baselined), 1 findings or stale baseline
entries, 2 usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

DEFAULT_BASELINE = os.path.join(_HERE, "mxlint_baseline.json")


def _ensure_devices():
    """jaxpr passes need >= 4 devices (sharding-placement); simulate on
    CPU before jax imports, mirroring tests/conftest.py."""
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON document for CI")
    ap.add_argument("--github", action="store_true",
                    help="emit GitHub workflow ::error annotations "
                    "(one per finding / stale baseline entry)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default tools/mxlint_baseline"
                    ".json); 'none' disables suppression")
    ap.add_argument("--write-baseline", action="store_true",
                    help="add every CURRENT finding to the baseline "
                    "with --reason and exit 0")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="delete baseline entries (of the executed "
                    "passes) whose fingerprint no longer matches any "
                    "finding, rewrite the file, exit 0")
    ap.add_argument("--reason", default=None,
                    help="reason recorded with --write-baseline entries")
    ap.add_argument("--list", action="store_true",
                    help="list registered passes and exit")
    args = ap.parse_args(argv)

    _ensure_devices()
    from mxnet_tpu.analysis import Baseline, all_passes, run_passes

    registry = all_passes()
    if args.list:
        for name in sorted(registry):
            p = registry[name]
            print(f"{name:<22} [{p.ir:<5}] {p.description}")
        return 0

    names = None
    if args.passes:
        names = [n.strip() for n in args.passes.split(",") if n.strip()]
        unknown = [n for n in names if n not in registry]
        if unknown:
            print(f"unknown pass(es) {unknown}; have {sorted(registry)}",
                  file=sys.stderr)
            return 2

    baseline = None
    if args.baseline and args.baseline.lower() != "none":
        baseline = Baseline.load(args.baseline)

    t0 = time.perf_counter()
    timings = {}

    def progress(name):
        timings[name] = time.perf_counter()
        if not args.json:
            print(f"[mxlint] {name} ...", file=sys.stderr)

    findings, suppressed = run_passes(names, baseline=baseline,
                                      progress=progress)
    elapsed = time.perf_counter() - t0

    # stale = baselined fingerprints (for a pass we actually ran) that
    # matched nothing: the excused code moved or was fixed, so the entry
    # is noise and the reasoned-baseline file has stopped being honest.
    executed = set(registry) if names is None else set(names)
    matched = {f.fingerprint for f, _r in suppressed}
    stale = []
    if baseline is not None and not args.write_baseline:
        for fp, entry in sorted(baseline.entries.items()):
            pass_name = entry.get("pass")
            in_scope = (pass_name in executed) if pass_name \
                else names is None
            if in_scope and fp not in matched:
                stale.append(fp)

    if args.prune_baseline:
        if baseline is None:
            print("--prune-baseline needs a baseline file "
                  "(not --baseline none)", file=sys.stderr)
            return 2
        for fp in stale:
            del baseline.entries[fp]
        baseline.save(args.baseline)
        print(f"pruned {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} from "
              f"{args.baseline}")
        return 0

    if args.write_baseline:
        if not args.reason:
            print("--write-baseline needs --reason (every grandfathered "
                  "violation must explain itself)", file=sys.stderr)
            return 2
        baseline = baseline or Baseline(path=args.baseline)
        for f in findings:
            baseline.entries[f.fingerprint] = {
                "reason": args.reason, "pass": f.pass_name,
                "rule": f.rule, "path": f.path,
            }
        baseline.save(args.baseline)
        print(f"baselined {len(findings)} finding(s) into "
              f"{args.baseline}")
        return 0

    if args.json:
        print(json.dumps({
            "ok": not findings and not stale,
            "elapsed_s": round(elapsed, 3),
            "passes_run": sorted(registry) if names is None else names,
            "findings": [f.to_dict() for f in findings],
            "suppressed": [dict(f.to_dict(), baseline_reason=r)
                           for f, r in suppressed],
            "stale_baseline": stale,
        }, indent=2))
    elif args.github:
        # one ::error per finding so the workflow UI pins each to its
        # file/line; summary goes to stderr to stay out of the stream
        rel_baseline = os.path.relpath(args.baseline, _ROOT)
        for f in findings:
            print(f"::error file={f.path},line={f.line}::"
                  f"[{f.pass_name}.{f.rule}] {f.message}")
        for fp in stale:
            print(f"::error file={rel_baseline}::stale baseline entry "
                  f"{fp} matches no finding — fix or --prune-baseline")
        print(f"mxlint: {len(findings)} finding(s), {len(stale)} stale, "
              f"{len(suppressed)} baselined in {elapsed:.1f}s",
              file=sys.stderr)
    else:
        for f, r in suppressed:
            print(f"BASELINED {f}  (reason: {r})")
        for f in findings:
            print(f)
        for fp in stale:
            print(f"STALE baseline entry {fp} matches no finding — "
                  f"delete it or run --prune-baseline")
        n = len(findings)
        print(f"mxlint: {n} finding(s), {len(suppressed)} baselined, "
              f"{len(registry) if names is None else len(names)} "
              f"pass(es) in {elapsed:.1f}s")
        if not findings and not stale:
            print("mxlint: clean")
    return 1 if findings or stale else 0


if __name__ == "__main__":
    sys.exit(main())
