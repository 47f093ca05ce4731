"""Shared benchmark harness: warmup, timed windows, driver JSON line.

Measurement entry points hide nothing about the device: every row names
it (``device_fields``), ``require_accelerator`` refuses to time the CPU,
and a failing run re-raises after its row so the status is nonzero."""

from __future__ import annotations

import json
import statistics
import time

# Published per-chip peaks keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
# HBM). A device that is not in the table is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_fields():
    """The device as JAX reports it — part of every benchmark row."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_accelerator():
    """A measurement path that finds no chip fails; it does not fall back
    to the CPU. Returns ``device_fields()``."""
    dev = device_fields()
    if dev["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax found only the CPU, and a CPU timing is "
            "never recorded as a device metric")
    return dev


def device_peaks():
    """Peak FLOP/s and HBM bytes/s of the attached device kind."""
    kind = device_fields()["device_kind"]
    if kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no published peaks for device kind {kind!r}; add it to "
            "benchmarks.common.DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[kind]


def _quantile(sorted_vals, p):
    if not sorted_vals:
        return None
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    rank = (p / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = rank - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def telemetry_fields(step_times=None, compile_time_s=None):
    """Uniform bench-row telemetry columns, null-safe everywhere.

    ``step_time_p50/p95`` come from the measured ``step_times`` (seconds)
    when the caller timed its own steps, else from the telemetry
    registry's ``trainer/step_time_s`` histogram (populated when
    ``MXNET_TELEMETRY=1`` and the workload steps through a Trainer).
    ``compile_time_s`` falls back to the ``jax.monitoring`` compile-event
    total; ``hbm_peak_bytes`` is None on backends without memory stats
    (CPU).
    """
    fields = device_fields()
    fields.update({
        "step_time_p50": None,
        "step_time_p95": None,
        "compile_time_s": compile_time_s,
        "hbm_peak_bytes": None,
        "hbm_headroom_bytes": None,
        "amp_dtype": None,
        "remat_policy": None,
        "mesh_shape": None,
        "sharding": None,
        "shard_param_bytes_per_shard": None,
    })
    report = None
    try:
        from mxnet_tpu import telemetry as _tel

        report = _tel.report()
        fields["hbm_peak_bytes"] = _tel.hbm_peak_bytes()
        fields["hbm_headroom_bytes"] = _tel.hbm_headroom_bytes()
        info = _tel.run_info()
        fields["amp_dtype"] = info.get("amp_dtype")
        fields["remat_policy"] = info.get("remat_policy")
        # SPMD sharding columns (parallel.sharding): the mesh/rules the
        # row ran under and one device's share of the parameter bytes
        fields["mesh_shape"] = info.get("mesh_shape")
        fields["sharding"] = info.get("sharding")
        fields["shard_param_bytes_per_shard"] = _tel.registry().gauge(
            "shard/param_bytes_per_shard").value
    except Exception:  # noqa: BLE001 - telemetry must never kill a bench
        _tel = None
    if step_times:
        s = sorted(step_times)
        fields["step_time_p50"] = round(_quantile(s, 50), 6)
        fields["step_time_p95"] = round(_quantile(s, 95), 6)
    elif report is not None:
        fields["step_time_p50"] = report.get("step_time_p50")
        fields["step_time_p95"] = report.get("step_time_p95")
    if fields["compile_time_s"] is None and report is not None:
        fields["compile_time_s"] = report.get("compile_time_s")
    return fields


def infer_fields():
    """Decode-bench row columns from the ``infer/`` metric family
    (null-safe: all None/0 when the registry is empty). The recompile
    figure is the serving acceptance gate — it must be 0 after
    ``InferStep.warmup`` across the prompt-bucket menu."""
    fields = {
        "prefill_ms_p50": None,
        "decode_ms_per_token_p50": None,
        "infer_tokens_per_sec": None,
        "batch_occupancy": None,
        "queue_wait_ms_p50": None,
        "steady_state_recompiles": None,
        # continuous batching / paged KV columns (serving.
        # ContinuousBatcher): time-to-first-token, pool pressure,
        # admission flow and the backpressure/preemption counters
        "ttft_ms_p50": None,
        "ttft_ms_p95": None,
        "pages_in_use": None,
        "page_fragmentation": None,
        "admitted_per_iter_p50": None,
        "rejected_backpressure": None,
        "preempted": None,
    }
    try:
        from mxnet_tpu import telemetry as _tel

        snap = _tel.registry().snapshot()
        h = snap["histograms"]
        g = snap["gauges"]
        if "infer/prefill_ms" in h:
            fields["prefill_ms_p50"] = h["infer/prefill_ms"]["p50"]
        if "infer/decode_ms_per_token" in h:
            fields["decode_ms_per_token_p50"] = \
                h["infer/decode_ms_per_token"]["p50"]
        if "infer/queue_wait_ms" in h:
            fields["queue_wait_ms_p50"] = h["infer/queue_wait_ms"]["p50"]
        if "infer/ttft_ms" in h:
            fields["ttft_ms_p50"] = h["infer/ttft_ms"]["p50"]
            fields["ttft_ms_p95"] = h["infer/ttft_ms"]["p95"]
        if "infer/admitted_per_iter" in h:
            fields["admitted_per_iter_p50"] = \
                h["infer/admitted_per_iter"]["p50"]
        fields["infer_tokens_per_sec"] = g.get("infer/tokens_per_sec")
        fields["batch_occupancy"] = g.get("infer/batch_occupancy")
        fields["pages_in_use"] = g.get("infer/pages_in_use")
        fields["page_fragmentation"] = g.get("infer/page_fragmentation")
        fields["rejected_backpressure"] = snap["counters"].get(
            "infer/rejected_backpressure", 0)
        fields["preempted"] = snap["counters"].get("infer/preempted", 0)
        fields["steady_state_recompiles"] = snap["counters"].get(
            "compile/steady_state_recompiles", 0)
    except Exception:  # noqa: BLE001 - telemetry must never kill a bench
        pass
    return fields


def disagg_fields():
    """Disaggregated-serving bench-row columns from the ``disagg/``
    metric family plus the scaler counters (null-safe). NOTE the
    router-side registry only sees the router's half (per-class TTFT,
    fallback re-prefills, scale actions); worker-side adoption/push
    figures live in the worker processes and ride the health verb —
    benches report those separately."""
    fields = {
        "disagg_re_prefills": 0,
        "disagg_handoffs": 0,
        "kv_push_ms_p50": None,
        "kv_bytes": 0,
        "ttft_interactive_ms_p50": None,
        "ttft_interactive_ms_p95": None,
        "ttft_batch_ms_p50": None,
        "ttft_batch_ms_p95": None,
        "scale_up": 0,
        "scale_down": 0,
    }
    try:
        from mxnet_tpu import telemetry as _tel

        snap = _tel.registry().snapshot()
        h = snap["histograms"]
        c = snap["counters"]
        if "disagg/kv_push_ms" in h:
            fields["kv_push_ms_p50"] = h["disagg/kv_push_ms"]["p50"]
        if "disagg/ttft_interactive_ms" in h:
            fields["ttft_interactive_ms_p50"] = \
                h["disagg/ttft_interactive_ms"]["p50"]
            fields["ttft_interactive_ms_p95"] = \
                h["disagg/ttft_interactive_ms"]["p95"]
        if "disagg/ttft_batch_ms" in h:
            fields["ttft_batch_ms_p50"] = h["disagg/ttft_batch_ms"]["p50"]
            fields["ttft_batch_ms_p95"] = h["disagg/ttft_batch_ms"]["p95"]
        fields["disagg_re_prefills"] = c.get("disagg/re_prefills", 0)
        fields["disagg_handoffs"] = c.get("disagg/handoffs", 0)
        fields["kv_bytes"] = c.get("disagg/kv_bytes", 0)
        fields["scale_up"] = c.get("serve/scale_up", 0)
        fields["scale_down"] = c.get("serve/scale_down", 0)
    except Exception:  # noqa: BLE001 - telemetry must never kill a bench
        pass
    return fields


def trace_overhead_fields(run_fn, gate_pct=2.0, pairs=3):
    """Measure the distributed-tracing tax on a serving workload.

    Runs ``run_fn`` (a zero-arg callable driving one fixed batch of
    load) ``pairs`` times each with tracing forced OFF and forced ON
    (``serving.tracing.force`` — overrides ``MXTPU_TRACE`` for this
    process), interleaved so drift hits both arms equally, and reports
    the median-over-median overhead. Negative deltas (noise) clamp to
    0. Null-safe: any failure returns None columns rather than killing
    the bench row. ``trace_overhead_ok`` is the ≤``gate_pct`` gate the
    serving rows are accepted on."""
    fields = {"trace_overhead_pct": None, "trace_overhead_ok": None}
    try:
        from mxnet_tpu.serving import tracing as _tracing

        offs, ons = [], []
        try:
            for _ in range(pairs):
                _tracing.force(False)
                t0 = time.perf_counter()
                run_fn()
                offs.append(time.perf_counter() - t0)
                _tracing.force(True)
                t0 = time.perf_counter()
                run_fn()
                ons.append(time.perf_counter() - t0)
        finally:
            _tracing.force(None)
        off = statistics.median(offs)
        on = statistics.median(ons)
        pct = max(0.0, (on - off) / off * 100.0) if off > 0 else 0.0
        fields["trace_overhead_pct"] = round(pct, 2)
        fields["trace_overhead_ok"] = pct <= gate_pct
    except Exception:  # noqa: BLE001 - tracing must never kill a bench
        pass
    return fields


def run_bench(metric, unit, ceiling, step_fn, sync_fn, items_per_step,
              warmup=3, steps=20, windows=4):
    """Time ``step_fn`` on the accelerator and print the driver JSON line.

    ``sync_fn`` must end the work it is handed (``block_until_ready`` or a
    host fetch). The loop is split into ``windows`` windows; the MEDIAN
    window rate is the metric of record, with the best window and the
    full list reported alongside (a best-only figure selects favorable
    noise).

    Every row names the device and carries ``step_time_p50/p95``
    (per-step wall from the timed windows), ``compile_time_s``
    (warmup+compile wall) and ``hbm_peak_bytes``. No accelerator is an
    error; a failure prints an ``error`` row and re-raises, so the exit
    status is nonzero.
    """
    require_accelerator()
    try:
        t0 = time.perf_counter()
        for _ in range(warmup):
            out = step_fn()
        sync_fn(out)
        compile_s = time.perf_counter() - t0
        per = max(1, steps // windows)
        rates = []
        step_times = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(per):
                out = step_fn()
            sync_fn(out)
            elapsed = time.perf_counter() - t0
            rates.append(per * items_per_step / elapsed)
            step_times.append(elapsed / per)
    except Exception as e:
        row = {
            "metric": metric,
            "value": 0.0,
            "unit": unit,
            "error": f"{type(e).__name__}: {e}"[:300],
        }
        row.update(telemetry_fields())
        print(json.dumps(row))
        raise
    value = statistics.median(rates)
    row = {
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(value / ceiling, 4),
        "best": round(max(rates), 1),
        "windows": [round(r, 1) for r in rates],
    }
    row.update(telemetry_fields(step_times=step_times,
                                compile_time_s=round(compile_s, 3)))
    print(json.dumps(row))
    return value


def run_varlen_mode(step, epoch_batches, tokens_per_epoch, epochs=2):
    """Drive a variable-length workload through a ``TrainStep`` and
    account its compiles exactly.

    ``epoch_batches(epoch)`` yields ``(input0, ..., label)`` batch tuples;
    ``tokens_per_epoch`` is the valid-token count of one full pass. The
    step's ``compile_guard`` counts one signature per compiled program, so
    ``signatures_per_epoch`` is the compile count each epoch paid and the
    LAST epoch's rate is the steady-state figure (first epochs absorb the
    compiles unless the caller warmed up first)."""
    guard = step.compile_guard
    sig_marks = [guard.signatures]
    tps = None
    for ep in range(epochs):
        t0 = time.perf_counter()
        last = None
        for batch in epoch_batches(ep):
            last = step(*batch)
        if last is not None:
            float(last.asscalar())  # retire the epoch's async dispatches
        elapsed = time.perf_counter() - t0
        sig_marks.append(guard.signatures)
        tps = tokens_per_epoch / elapsed
    return {
        "signatures_per_epoch": [
            sig_marks[i + 1] - sig_marks[i] for i in range(epochs)],
        "signatures_total": sig_marks[-1],
        "steady_state_recompiles": guard.steady_state_recompiles,
        "steady_tokens_per_sec": round(tps, 1),
    }


def device_us(fn, args, iters=6):
    """Per-call DEVICE op time (us) by summing the profiler's device-lane
    events: wall columns of small ops sit at the dispatch floor, so only
    profiler-counted device time can see an op regression. Ported from
    benchmarks/bench_linear_ce.py (where it drove the CE regime sweep)."""
    import glob
    import gzip
    import json as _json
    import shutil
    import tempfile

    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    d = tempfile.mkdtemp(prefix="opperf_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = glob.glob(f"{d}/plugins/profile/*/*.trace.json.gz")[0]
        with gzip.open(path) as f:
            tr = _json.load(f)
        # locate the device op lane from the trace's OWN metadata
        # ('/device:...' process, 'XLA Ops' thread) instead of a
        # hardcoded pid/tid that silently reads 0.0 on other rigs
        dev_pids = set()
        ops_lanes = set()
        for e in tr["traceEvents"]:
            if e.get("ph") != "M":
                continue
            name = (e.get("args") or {}).get("name", "")
            if e.get("name") == "process_name" and \
                    name.startswith("/device:"):
                dev_pids.add(e.get("pid"))
            elif e.get("name") == "thread_name" and name == "XLA Ops":
                ops_lanes.add((e.get("pid"), e.get("tid")))
        lanes = {ln for ln in ops_lanes if ln[0] in dev_pids}
        if not lanes:
            return None  # no device lane found: report n/a, never 0.0
        tot = 0.0
        for e in tr["traceEvents"]:
            if e.get("ph") == "X" and \
                    (e.get("pid"), e.get("tid")) in lanes:
                tot += e.get("dur", 0)
        return tot / iters if tot > 0 else None
    finally:
        shutil.rmtree(d, ignore_errors=True)
