"""Driver benchmark: BERT-base pretrain throughput on one chip.

Measures tokens/sec through the fully-jitted sharded TrainStep (forward +
backward + optimizer in ONE XLA executable, donated buffers) — BASELINE.md
config 3, the metric of record "tokens/sec/chip BERT-base pretrain".
``steps_per_call=STEPS_PER_CALL`` runs that many full optimizer steps on
distinct microbatches per dispatch via a device-side lax.scan
(parallel/step.py), so one host dispatch feeds the device for many steps.

Prints ONE JSON line: {"metric", "value", "unit", "platform",
"device_kind", "device_count", ...}. ``value`` is the MEDIAN of the timing
windows; the best window and the full per-window list are included as
extra keys. This is a measurement entry point, so nothing here hides the
device: no accelerator, an import failure or any error in the run is a
traceback and a nonzero exit, never a smaller batch, another backend or a
row with an ``error`` key and status 0. ``chip_smoke.py`` builds the same
model through ``_build``.
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np

METRIC = "bert_base_pretrain_tokens_per_sec_per_chip"
STEPS_PER_CALL = 40
BATCH = 64
SEQ = 128
WINDOWS = 4
CALLS_PER_WINDOW = 4

BERT_BASE = dict(vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512, dropout=0.1)


def _build(batch, seq, steps_per_call=STEPS_PER_CALL, mesh=None,
           sharding=None, **net_overrides):
    """BERT-base + MLM-style loss + AdamW behind one ``TrainStep``.
    ``net_overrides`` replace ``BERT_BASE`` entries (the smoke's rehearsal
    sizes, ``dropout=0.0`` for a cross-mesh comparison); ``mesh`` /
    ``sharding`` go to ``TrainStep`` unchanged."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel
    from mxnet_tpu.parallel import TrainStep

    cfg = dict(BERT_BASE, **net_overrides)
    net = BERTModel(**cfg)
    net.initialize()
    net._probe_shapes(mx.nd.zeros((2, 8), dtype="int32"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    word_w = net.word_embed.weight

    class _PretrainLoss:
        """MLM-style CE against the tied embedding (exercises the full
        encoder + vocab-size matmul like real pretraining). The logits are
        materialized: at B*S=8192, V=30522 they fit, and XLA fuses the
        softmax passes. Use ``linear_cross_entropy`` when they do not fit
        (bigger vocab / longer batch)."""

        def __call__(self, seq_out, pooled, label):
            w = word_w.data()
            logits = seq_out.reshape(-1, seq_out.shape[-1]).dot(w.T)
            return ce(logits, label.reshape(-1))

    # bf16 compute + f32 masters = the reference's "BERT + AMP" config 3
    step = TrainStep(net, _PretrainLoss(), opt.AdamW(learning_rate=1e-4),
                     mesh=mesh, sharding=sharding,
                     compute_dtype="bfloat16", state_dtype="bfloat16",
                     steps_per_call=steps_per_call)
    rng = np.random.RandomState(0)
    n = batch * steps_per_call  # DISTINCT microbatches per dispatch
    vocab = cfg["vocab_size"]
    ids = mx.nd.array(rng.randint(0, vocab, (n, seq)), dtype="int32")
    labels = mx.nd.array(rng.randint(0, vocab, (n, seq)), dtype="int32")
    return step, ids, labels


def main():
    from benchmarks.common import require_accelerator, telemetry_fields

    require_accelerator()
    step, ids, labels = _build(BATCH, SEQ)
    # warmup / compile, retired before the clock starts
    t0 = time.perf_counter()
    for _ in range(3):
        loss = step(ids, labels)
    loss.data.block_until_ready()
    compile_s = time.perf_counter() - t0
    tokens_per_window = CALLS_PER_WINDOW * STEPS_PER_CALL * BATCH * SEQ
    rates = []
    step_times = []  # per-optimizer-step wall, from SYNCED windows
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(CALLS_PER_WINDOW):
            loss = step(ids, labels)
        loss.data.block_until_ready()
        elapsed = time.perf_counter() - t0
        rates.append(tokens_per_window / elapsed)
        # async dispatch returns immediately, so only the synced
        # window total is an honest wall figure; per-call splits
        # would report dispatch latency as step time
        step_times.append(elapsed / (CALLS_PER_WINDOW * STEPS_PER_CALL))
    if not np.isfinite(float(loss.asscalar())):
        raise RuntimeError("BERT-base loss is not finite")
    row = {
        "metric": METRIC,
        "value": round(statistics.median(rates), 1),
        "unit": "tokens/sec",
        "best": round(max(rates), 1),
        "windows": [round(r, 1) for r in rates],
    }
    row.update(telemetry_fields(step_times=step_times,
                                compile_time_s=round(compile_s, 3)))
    print(json.dumps(row))


def _watchdog(seconds=540):
    """A run that hangs would leave the driver with NO line at all. A
    daemon THREAD (signal handlers can't preempt a main thread blocked
    inside a C call) emits an error JSON row and hard-exits nonzero if
    the bench exceeds the budget, so the row and the status agree."""
    import os
    import threading

    def boom():
        from benchmarks.common import telemetry_fields

        row = {
            "metric": METRIC,
            "value": 0.0,
            "unit": "tokens/sec",
            "error": f"watchdog: no result within {seconds}s",
        }
        row.update(telemetry_fields())
        print(json.dumps(row), flush=True)
        os._exit(1)

    t = threading.Timer(seconds, boom)
    t.daemon = True
    t.start()
    return t


if __name__ == "__main__":
    _timer = _watchdog()
    main()
    # a legitimately slow-but-successful run must not be shot mid-teardown
    _timer.cancel()
