#!/usr/bin/env python
"""Pretty-print a JSONL telemetry dump (``events.jsonl`` from
``mx.telemetry``).

Aggregates spans by name (count, total/mean/p50/p95/p99/max), lists
instant events (checkpoint commits, watchdog stalls), and — when pointed
at a telemetry DIRECTORY — also surfaces ``heartbeat.json`` and
``report.json`` if present.

Usage:
  python tools/telemetry_report.py telemetry/            # a dump dir
  python tools/telemetry_report.py telemetry/events.jsonl
  python tools/telemetry_report.py events.jsonl --top 20 --sort total
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Every metric family the package emits, and which section of this tool
# surfaces it. mxlint's telemetry-names pass fails CI when code emits a
# family missing here (it would silently vanish from every report) or
# when an entry here is dead. Families mapped to "Host-side training"
# print through _print_host_family below; the serving-era families have
# dedicated sections.
KNOWN_METRIC_FAMILIES = {
    "compile": "Compile (shape stability)",
    "infer": "Inference / serving",
    "serve": "Self-healing serving",
    "launch": "Self-healing serving",
    "transport": "Cross-process transport",
    "disagg": "Disaggregated serving",
    "shard": "SPMD sharding",
    "trainer": "Host-side training",
    "trainstep": "Host-side training",
    "kvstore": "Host-side training",
    "input": "Host-side training",
    "device": "Host-side training",
    "watchdog": "Host-side training",
    "jax": "Compile (shape stability)",
    "fleet": "Fleet observability",
}

# Span/instant families (Chrome-trace names are dotted); spans aggregate
# generically in the Spans table, so membership here is the emitted
# surface the consistency pass checks, not a formatting choice.
KNOWN_SPAN_FAMILIES = {
    "checkpoint", "dataloader", "disagg", "estimator", "imperative",
    "infer", "input", "kvstore", "launch", "sched", "serve", "trace",
    "train", "trainer", "trainstep", "transport", "watchdog",
}


def _quantile(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    rank = (p / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = rank - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def load_events(path):
    """Yield parsed JSONL records, skipping torn lines (the stream is
    append-only and may end mid-write after a crash — that is the point
    of the format)."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except ValueError:
                print(f"  (skipping torn line {lineno})", file=sys.stderr)


def summarize(events):
    spans = {}
    instants = []
    for e in events:
        ph = e.get("ph")
        if ph == "X":
            spans.setdefault(e.get("name", "?"), []).append(
                float(e.get("dur", 0.0)))
        elif ph == "i":
            instants.append(e)
    return spans, instants


def format_spans(spans, top=None, sort="total"):
    rows = []
    for name, durs in spans.items():
        s = sorted(durs)
        total = sum(durs)
        rows.append({
            "name": name,
            "count": len(durs),
            "total_ms": total / 1e3,
            "mean_ms": total / len(durs) / 1e3,
            "p50_ms": _quantile(s, 50) / 1e3,
            "p95_ms": _quantile(s, 95) / 1e3,
            "p99_ms": _quantile(s, 99) / 1e3,
            "max_ms": s[-1] / 1e3,
        })
    keys = {"total": "total_ms", "count": "count", "mean": "mean_ms",
            "p95": "p95_ms", "name": "name"}
    rev = sort != "name"
    rows.sort(key=lambda r: r[keys.get(sort, "total_ms")], reverse=rev)
    if top:
        rows = rows[:top]
    hdr = (f"{'Span':<32}{'Count':>8}{'Total(ms)':>12}{'Mean(ms)':>10}"
           f"{'p50':>9}{'p95':>9}{'p99':>9}{'Max':>9}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['name']:<32}{r['count']:>8}{r['total_ms']:>12.2f}"
            f"{r['mean_ms']:>10.3f}{r['p50_ms']:>9.3f}{r['p95_ms']:>9.3f}"
            f"{r['p99_ms']:>9.3f}{r['max_ms']:>9.3f}")
    return "\n".join(lines)


def _print_json_file(path, title):
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            data = json.load(f)
    except ValueError:
        return
    print(f"\n== {title} ({path}) ==")
    print(json.dumps(data, indent=2, default=str)[:4000])


def _print_host_families(report_path):
    """Surface the host-side training families (trainer/, kvstore/,
    input/, device/, watchdog/) from a ``report.json`` registry
    snapshot — previously only visible in the raw report dump."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    fams = tuple(f + "/" for f, sec in KNOWN_METRIC_FAMILIES.items()
                 if sec == "Host-side training")
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith(fams)}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith(fams)}
    hists = {k: v for k, v in report.get("histograms", {}).items()
             if k.startswith(fams)}
    if not counters and not gauges and not hists:
        return
    print("\n== Host-side training ==")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(hists):
        h = hists[k]
        print(f"  {k:<38} p50={h.get('p50')} p95={h.get('p95')} "
              f"n={h.get('count')}")


def _print_compile_family(report_path):
    """Surface the ``compile/`` metric family (shape-stability spine:
    signatures compiled, post-warmup recompiles, persistent-cache reuse)
    from a ``report.json`` registry snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("compile/")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("compile/")}
    jax_compile = report.get("histograms", {}).get("jax/compile_time_s")
    if not counters and not gauges and not jax_compile:
        return
    print("\n== Compile (shape stability) ==")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    if jax_compile:
        print(f"  {'jax/compile_time_s total':<38} "
              f"{jax_compile.get('sum', 0.0):.3f}s over "
              f"{jax_compile.get('count', 0)} events")
    recompiles = counters.get("compile/steady_state_recompiles", 0)
    if recompiles:
        print(f"  WARNING: {recompiles} steady-state recompile(s) — "
              "shape churn after warmup (bucket/pad inputs)")


def _print_infer_family(report_path):
    """Surface the ``infer/`` metric family (serving spine: prefill /
    per-token decode latency, throughput, batcher admission wait and slot
    occupancy) from a ``report.json`` registry snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("infer/")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("infer/")}
    hists = {k: v for k, v in report.get("histograms", {}).items()
             if k.startswith("infer/")}
    if not counters and not gauges and not hists:
        return
    print("\n== Inference / serving ==")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(hists):
        h = hists[k]
        print(f"  {k:<38} p50={h.get('p50')} p95={h.get('p95')} "
              f"n={h.get('count')}")
    rejected = counters.get("infer/rejected_backpressure", 0)
    if rejected:
        print(f"  WARNING: {rejected} request(s) rejected by admission "
              "control — raise MXTPU_PAGES or relax MXTPU_ADMIT_* "
              "thresholds if the pool is undersized")
    preempted = counters.get("infer/preempted", 0)
    if preempted:
        print(f"  WARNING: {preempted} mid-decode preemption(s) — the "
              "page pool oversubscribes more than the workload tolerates "
              "(MXTPU_PAGES / MXTPU_ADMIT_FREE_PAGES)")


def _print_serve_family(report_path):
    """Surface the ``serve/`` metric family (self-healing serving plane:
    hot weight swaps, replica failovers, transparent retries, dropped
    requests, injected faults) from a ``report.json`` snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith(("serve/", "launch/"))}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("serve/")}
    version = report.get("weights_version")
    if not counters and not gauges and not version:
        return
    print("\n== Self-healing serving ==")
    if version:
        print(f"  {'weights_version':<38} {version}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    dropped = counters.get("serve/dropped", 0)
    if dropped:
        print(f"  WARNING: {dropped} request(s) dropped after retry "
              "exhaustion — check replica health and MXTPU_RETRY_MAX")


def _print_transport_family(report_path):
    """Surface the ``transport/`` metric family (cross-process serving
    plane: per-call RPC latency, connect retries, dead connections) plus
    the router's worker-facing shed counters from a ``report.json``
    snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("transport/")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("transport/")}
    hists = {k: v for k, v in report.get("histograms", {}).items()
             if k.startswith("transport/")}
    sheds = {k: v for k, v in report.get("counters", {}).items()
             if k.startswith("serve/shed_")}
    if not counters and not gauges and not hists and not sheds:
        return
    print("\n== Cross-process transport ==")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(hists):
        h = hists[k]
        print(f"  {k:<38} p50={h.get('p50')} p95={h.get('p95')} "
              f"n={h.get('count')}")
    for k in sorted(sheds):
        print(f"  {k:<38} {sheds[k]}")
    shed_total = sum(sheds.values())
    if shed_total:
        print(f"  WARNING: {shed_total} request(s) shed at router "
              "admission — every replica was degraded; scale out or "
              "relax MXTPU_SHED_* thresholds")
    errors = counters.get("transport/errors", 0)
    if errors:
        print(f"  WARNING: {errors} dead worker connection(s) — check "
              "worker logs/heartbeats for crashes or partitions")


def _print_disagg_family(report_path):
    """Surface the ``disagg/`` metric family (disaggregated serving:
    KV handoffs adopted vs re-prefill fallbacks, push latency and
    bytes, per-class TTFT, scale actions) from a ``report.json``
    snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("disagg/")
                or k in ("serve/scale_up", "serve/scale_down")}
    hists = {k: v for k, v in report.get("histograms", {}).items()
             if k.startswith("disagg/")}
    if not counters and not hists:
        return
    print("\n== Disaggregated serving ==")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    for k in sorted(hists):
        h = hists[k]
        print(f"  {k:<38} p50={h.get('p50')} p95={h.get('p95')} "
              f"n={h.get('count')}")
    re_prefills = counters.get("disagg/re_prefills", 0)
    handoffs = counters.get("disagg/handoffs", 0)
    if re_prefills and re_prefills >= max(handoffs, 1):
        print(f"  WARNING: {re_prefills} re-prefill(s) vs {handoffs} "
              "adopted handoff(s) — pushes are failing (dead prefill "
              "workers, dropped links, or mismatched model geometry); "
              "the fleet is paying prefill twice")


def _print_prefix_section(report_path):
    """Surface the prefix-caching slice of the ``infer/``/``serve/``
    families (radix-trie hit rate, tokens served from cached KV, pages
    shared across requests, copy-on-write copies, affinity placements)
    from a ``report.json`` snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    names = ("infer/prefix_tokens_saved", "infer/prefix_cow_copies",
             "serve/prefix_affinity")
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k in names}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k in ("infer/prefix_hit_rate", "infer/pages_shared")}
    if not counters and not gauges:
        return
    print("\n== Prefix caching ==")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    hit_rate = gauges.get("infer/prefix_hit_rate")
    saved = counters.get("infer/prefix_tokens_saved", 0)
    if saved:
        print(f"  prefill tokens served from cached KV: {saved}")
    if hit_rate is not None and hit_rate == 0.0 and saved == 0:
        print("  WARNING: the prefix cache is enabled but never hits — "
              "prompts may be unique per request (disable with "
              "MXTPU_PREFIX_CACHE=0 to reclaim pool pages)")


def _print_spec_section(report_path):
    """Surface the speculative-decoding slice of the ``infer/`` family
    (per-round accepted-draft length, draft-dispatch latency, and
    whether the Pallas paged flash kernels are active) from a
    ``report.json`` snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    hists = {k: v for k, v in report.get("histograms", {}).items()
             if k in ("infer/spec_accept_len", "infer/spec_draft_ms")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k == "infer/flash_kernel"}
    if not hists and not gauges:
        return
    print("\n== Speculative decoding ==")
    for k in sorted(gauges):
        on = "on (Pallas paged flash)" if gauges[k] else "off (dense)"
        print(f"  {k:<38} {on}")
    for k in sorted(hists):
        h = hists[k]
        print(f"  {k:<38} p50={h.get('p50')} p95={h.get('p95')} "
              f"n={h.get('count')}")
    acc = hists.get("infer/spec_accept_len")
    if acc and acc.get("count") and acc.get("sum", 0.0) == 0.0:
        print("  WARNING: the draft model's proposals are NEVER accepted "
              "— the target re-scores every token and speculation only "
              "adds draft latency; check that the draft tracks the "
              "target (same tokenizer/data) or lower MXTPU_SPEC_K")


def _print_shard_family(report_path):
    """Surface the ``shard/`` metric family (SPMD sharding spine: mesh
    shape, global vs per-shard parameter bytes, collective-traffic
    estimate, host-allreduce skips) from a ``report.json`` snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("shard/")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("shard/")}
    mesh = report.get("mesh_shape")
    if not counters and not gauges and not mesh:
        return
    print("\n== SPMD sharding ==")
    if mesh:
        print(f"  {'mesh_shape':<38} {mesh}")
    if report.get("sharding"):
        print(f"  {'sharding':<38} {report['sharding']}")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    total = gauges.get("shard/param_bytes_total")
    per = gauges.get("shard/param_bytes_per_shard")
    if total and per and per < total:
        print(f"  params per shard: {per / total:.1%} of the full tree "
              f"({total / 1e6:.1f} MB -> {per / 1e6:.1f} MB/device)")


def _print_fleet_family(report_path):
    """Surface the ``fleet/`` metric family (the telemetry scrape loop:
    scrapes completed, scrape errors, replicas seen, per-request SLO
    burn) from a ``report.json`` snapshot."""
    if not os.path.exists(report_path):
        return
    try:
        with open(report_path) as f:
            report = json.load(f)
    except ValueError:
        return
    counters = {k: v for k, v in report.get("counters", {}).items()
                if k.startswith("fleet/")
                or k.startswith("serve/slo_burn_")}
    gauges = {k: v for k, v in report.get("gauges", {}).items()
              if k.startswith("fleet/")}
    if not counters and not gauges:
        return
    print("\n== Fleet observability ==")
    for k in sorted(gauges):
        print(f"  {k:<38} {gauges[k]}")
    for k in sorted(counters):
        print(f"  {k:<38} {counters[k]}")
    errors = counters.get("fleet/scrape_errors", 0)
    scrapes = counters.get("fleet/scrapes", 0)
    if errors and errors >= max(scrapes, 1):
        print(f"  WARNING: {errors} scrape error(s) vs {scrapes} "
              "completed scrape(s) — workers are unreachable from the "
              "telemetry loop (check transport health)")
    burn = sum(v for k, v in counters.items()
               if k.startswith("serve/slo_burn_"))
    if burn:
        print(f"  WARNING: {burn} request(s) finished past their class "
              "SLO — inspect per-request phase breakdowns "
              "(GenerationResult.phases) to attribute the overrun")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("path", help="events.jsonl file or telemetry directory")
    ap.add_argument("--top", type=int, default=None,
                    help="show only the top N spans")
    ap.add_argument("--sort", default="total",
                    choices=["total", "count", "mean", "p95", "name"])
    args = ap.parse_args(argv)

    path = args.path
    directory = None
    if os.path.isdir(path):
        directory = path
        path = os.path.join(path, "events.jsonl")
    if not os.path.exists(path):
        ap.error(f"no events file at {path}")

    spans, instants = summarize(load_events(path))
    if not spans and not instants:
        print(f"{path}: no events")
        return 0
    print(f"== Spans ({path}) ==")
    if spans:
        print(format_spans(spans, top=args.top, sort=args.sort))
    else:
        print("(none)")
    if instants:
        print(f"\n== Instant events ({len(instants)}) ==")
        for e in instants:
            args_str = json.dumps(e.get("args", {}), default=str)
            print(f"  ts={e.get('ts', 0) / 1e6:>10.3f}s  "
                  f"{e.get('name', '?'):<28} {args_str}")
    if directory:
        _print_json_file(os.path.join(directory, "heartbeat.json"),
                         "Heartbeat")
        _print_json_file(os.path.join(directory, "report.json"), "Report")
        _print_host_families(os.path.join(directory, "report.json"))
        _print_compile_family(os.path.join(directory, "report.json"))
        _print_infer_family(os.path.join(directory, "report.json"))
        _print_prefix_section(os.path.join(directory, "report.json"))
        _print_spec_section(os.path.join(directory, "report.json"))
        _print_shard_family(os.path.join(directory, "report.json"))
        _print_serve_family(os.path.join(directory, "report.json"))
        _print_transport_family(os.path.join(directory, "report.json"))
        _print_disagg_family(os.path.join(directory, "report.json"))
        _print_fleet_family(os.path.join(directory, "report.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
