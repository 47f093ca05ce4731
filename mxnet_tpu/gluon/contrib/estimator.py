"""Gluon Estimator: high-level fit loop with event handlers.

Reference: ``python/mxnet/gluon/contrib/estimator/`` [unverified] —
``Estimator.fit`` drives train/val epochs and dispatches lifecycle events
(TrainBegin/EpochBegin/BatchBegin/BatchEnd/EpochEnd/TrainEnd) to handler
objects. The TPU build keeps the same handler contracts; the training step
itself runs through the standard autograd + Trainer path (hybridize the net
for the staged XLA step).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Sequence

from ... import autograd, metric as _metric
from ... import telemetry as _tel
from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from ..trainer import Trainer

__all__ = [
    "Estimator", "TrainBegin", "TrainEnd", "EpochBegin", "EpochEnd",
    "BatchBegin", "BatchEnd", "StoppingHandler", "MetricHandler",
    "ValidationHandler", "LoggingHandler", "CheckpointHandler",
    "EarlyStoppingHandler",
]


# ------------------------------------------------------------ event mixins
class TrainBegin:
    def train_begin(self, estimator, *args, **kwargs):
        pass


class TrainEnd:
    def train_end(self, estimator, *args, **kwargs):
        pass


class EpochBegin:
    def epoch_begin(self, estimator, *args, **kwargs):
        pass


class EpochEnd:
    def epoch_end(self, estimator, *args, **kwargs):
        pass


class BatchBegin:
    def batch_begin(self, estimator, *args, **kwargs):
        pass


class BatchEnd:
    def batch_end(self, estimator, *args, **kwargs):
        pass


# -------------------------------------------------------- builtin handlers
class StoppingHandler(TrainBegin, BatchEnd, EpochEnd):
    """Stop on max_epoch / max_batch (reference default handler)."""

    def __init__(self, max_epoch=None, max_batch=None):
        self.max_epoch = max_epoch
        self.max_batch = max_batch
        self.current_batch = 0
        self.current_epoch = 0
        self.stop_training = False

    def train_begin(self, estimator, *args, **kwargs):
        self.current_batch = 0
        self.current_epoch = 0

    def batch_end(self, estimator, *args, **kwargs):
        self.current_batch += 1
        if self.max_batch and self.current_batch >= self.max_batch:
            self.stop_training = True

    def epoch_end(self, estimator, *args, **kwargs):
        self.current_epoch += 1
        if self.max_epoch and self.current_epoch >= self.max_epoch:
            self.stop_training = True


class MetricHandler(EpochBegin, BatchEnd):
    """Reset metrics at epoch begin, update at batch end."""

    def __init__(self, metrics):
        self.metrics = _as_list(metrics)

    def epoch_begin(self, estimator, *args, **kwargs):
        for m in self.metrics:
            m.reset()

    def batch_end(self, estimator, *args, **kwargs):
        pred = kwargs.get("pred")
        label = kwargs.get("label")
        loss = kwargs.get("loss")
        for m in self.metrics:
            if isinstance(m, _metric.Loss):
                m.update(0, loss)
            else:
                m.update(label, pred)


class ValidationHandler(TrainBegin, BatchEnd, EpochEnd):
    """Run validation every ``epoch_period`` epochs (or batch_period)."""

    def __init__(self, val_data, eval_fn, epoch_period=1, batch_period=None,
                 priority=-1000):
        self.val_data = val_data
        self.eval_fn = eval_fn
        self.epoch_period = epoch_period
        self.batch_period = batch_period
        self.current_batch = 0
        self.current_epoch = 0
        self.priority = priority

    def train_begin(self, estimator, *args, **kwargs):
        self.current_batch = 0
        self.current_epoch = 0

    def batch_end(self, estimator, *args, **kwargs):
        self.current_batch += 1
        if self.batch_period and self.current_batch % self.batch_period == 0:
            self.eval_fn(self.val_data)

    def epoch_end(self, estimator, *args, **kwargs):
        self.current_epoch += 1
        if self.epoch_period and self.current_epoch % self.epoch_period == 0:
            self.eval_fn(self.val_data)


class LoggingHandler(TrainBegin, TrainEnd, EpochBegin, EpochEnd, BatchEnd):
    """Periodic speed/metric logging (reference LOG_PER_EPOCH/LOG_PER_BATCH)."""

    LOG_PER_EPOCH = 1
    LOG_PER_BATCH = 2

    def __init__(self, log_interval="epoch", metrics=None):
        self.metrics = _as_list(metrics) if metrics else []
        if log_interval == "epoch":
            self.log_interval = self.LOG_PER_EPOCH
        else:
            self.log_interval = int(log_interval)
        self.batch_index = 0
        self.current_epoch = 0
        self._logger = logging.getLogger(__name__)
        self.processed_samples = 0
        self.last_tic = 0.0

    def train_begin(self, estimator, *args, **kwargs):
        self.last_tic = time.time()
        self._logger.info("Training begin")

    def train_end(self, estimator, *args, **kwargs):
        self._logger.info("Training end: %d epochs", self.current_epoch)

    def epoch_begin(self, estimator, *args, **kwargs):
        self.batch_index = 0
        self.processed_samples = 0
        self.last_tic = time.time()

    def batch_end(self, estimator, *args, **kwargs):
        self.batch_index += 1
        batch = kwargs.get("batch")
        if batch is not None:
            self.processed_samples += _batch_size(batch)
        if self.log_interval != self.LOG_PER_EPOCH and \
                self.batch_index % self.log_interval == 0:
            self._log("Batch[%d]" % self.batch_index)

    def epoch_end(self, estimator, *args, **kwargs):
        if self.log_interval == self.LOG_PER_EPOCH:
            self._log("Epoch[%d]" % self.current_epoch)
        self.current_epoch += 1

    def _log(self, head):
        elapsed = max(time.time() - self.last_tic, 1e-9)
        parts = [f"{head} speed={self.processed_samples / elapsed:.1f} samples/s"]
        for m in self.metrics:
            name, value = m.get()
            parts.append(f"{name}={value}")
        self._logger.info(" ".join(str(p) for p in parts))
        self.last_tic = time.time()


class CheckpointHandler(TrainBegin, BatchEnd, EpochEnd):
    """Save params every ``epoch_period`` epochs via net.save_parameters."""

    def __init__(self, model_dir, model_prefix="model", epoch_period=1,
                 max_checkpoints=5):
        import os

        self.model_dir = model_dir
        self.model_prefix = model_prefix
        self.epoch_period = epoch_period
        self.max_checkpoints = max_checkpoints
        self.current_epoch = 0
        self.saved = []
        os.makedirs(model_dir, exist_ok=True)

    def epoch_end(self, estimator, *args, **kwargs):
        import os

        self.current_epoch += 1
        if self.current_epoch % self.epoch_period:
            return
        path = os.path.join(
            self.model_dir,
            f"{self.model_prefix}-epoch{self.current_epoch}.params",
        )
        estimator.net.save_parameters(path)
        self.saved.append(path)
        while len(self.saved) > self.max_checkpoints:
            old = self.saved.pop(0)
            if os.path.exists(old):
                os.remove(old)


class EarlyStoppingHandler(TrainBegin, EpochEnd, TrainEnd):
    """Stop when ``monitor`` stops improving (reference semantics: mode
    auto-resolves from the metric name — 'acc'/'f1' max, losses min)."""

    def __init__(self, monitor, min_delta=0, patience=0, mode="auto"):
        self.monitor = monitor
        self.min_delta = min_delta
        self.patience = patience
        if mode == "auto":
            name = monitor.get()[0] if hasattr(monitor, "get") else str(monitor)
            mode = "max" if any(k in name.lower()
                                for k in ("acc", "f1", "score")) else "min"
        self.mode = mode
        self.best = None
        self.wait = 0
        self.stop_training = False
        self.stopped_epoch = None
        self.current_epoch = 0

    def _improved(self, value):
        if self.best is None:
            return True
        if self.mode == "max":
            return value > self.best + self.min_delta
        return value < self.best - self.min_delta

    def epoch_end(self, estimator, *args, **kwargs):
        value = self.monitor.get()[1]
        if self._improved(value):
            self.best = value
            self.wait = 0
        else:
            self.wait += 1
            if self.wait > self.patience:
                self.stop_training = True
                self.stopped_epoch = self.current_epoch
        self.current_epoch += 1

    def train_end(self, estimator, *args, **kwargs):
        if self.stopped_epoch is not None:
            logging.getLogger(__name__).info(
                "Early stopping at epoch %d (best %s=%s)",
                self.stopped_epoch, self.monitor.get()[0], self.best,
            )


# ---------------------------------------------------------------- Estimator
class Estimator:
    """High-level training facade (reference: ``gluon.contrib.estimator``).

    >>> est = Estimator(net, loss, train_metrics=mx.metric.Accuracy(),
    ...                 trainer=trainer)
    >>> est.fit(train_data, val_data, epochs=2)
    """

    def __init__(self, net, loss, train_metrics=None, val_metrics=None,
                 trainer=None, context=None, evaluation_loss=None,
                 train_step=None):
        self.net = net
        self.loss = loss
        self.evaluation_loss = evaluation_loss or loss
        self.train_metrics = _as_list(train_metrics) if train_metrics else []
        self.val_metrics = _as_list(val_metrics) if val_metrics else \
            [type(m)() for m in self.train_metrics]
        self.train_loss_metric = _metric.Loss("train_loss")
        # train_step: a parallel.TrainStep over the SAME net — fit then
        # drives the fused sharded XLA step (forward+backward+collectives+
        # optimizer in ONE donated program, mesh/sharding rules included)
        # instead of the eager autograd+Trainer path. No Trainer is built
        # in that mode (the step owns the optimizer); per-batch pred/label
        # stay on device, so only Loss-type train metrics update.
        self.train_step = train_step
        if train_step is not None:
            self.trainer = trainer
        else:
            self.trainer = trainer or Trainer(
                net.collect_params(), "adam", {"learning_rate": 1e-3}
            )
        self.context = context
        self.stop_training = False

    # -------------------------------------------------------------- predict
    def predict(self, data, batch_fn=None, engine=None):
        """Inference pass: run the net in predict mode over ``data`` and
        return the list of per-batch outputs.

        ``data`` yields batches — bare arrays (fed as the single input)
        or tuples (fed positionally; pass ``batch_fn(batch) -> inputs
        tuple`` to strip labels from a training loader). ``engine``: an
        optional ``parallel.infer.InferStep`` over the same net — batches
        then run through its jitted, shape-guarded forward (warm it with
        the loader's signature menu for a compile-free pass) instead of
        the eager/hybridized path.

        ``engine`` may also be a serving BATCHER (anything with
        ``submit()`` — ``serving.make_batcher`` builds the paged-KV
        ``ContinuousBatcher``): each batch's rows are then submitted
        as individual generation requests through iteration-level
        scheduling and the per-batch output is a ``(tokens (B, max_new),
        lengths (B,))`` NDArray pair, trimmed/padded exactly like
        ``InferStep.decode_n``. Batches are ``src`` arrays or ``(src,
        valid_length)`` tuples in that mode."""
        if engine is not None and hasattr(engine, "submit"):
            return self._predict_generate(data, batch_fn, engine)
        runner = engine if engine is not None else self.net
        outs = []
        for batch in data:
            if batch_fn is not None:
                inputs = batch_fn(batch)
            elif isinstance(batch, (list, tuple)):
                inputs = batch
            else:
                inputs = (batch,)
            with (_tel.span("estimator.predict_batch") if _tel._ENABLED
                  else _tel.NULL_SPAN):
                outs.append(runner(*inputs))
        return outs

    def _predict_generate(self, data, batch_fn, batcher):
        """Generation pass through a serving batcher: rows fan out as
        requests (continuous batching keeps the decode batch full across
        batch boundaries), results gather back into per-batch
        ``(tokens, lengths)`` pairs."""
        import numpy as np

        from ...ndarray.ndarray import NDArray
        from ... import ndarray as _nd

        outs = []
        for batch in data:
            if batch_fn is not None:
                batch = batch_fn(batch)
            if isinstance(batch, (list, tuple)):
                src = batch[0]
                vl = batch[1] if len(batch) > 1 else None
            else:
                src, vl = batch, None
            src = src.asnumpy() if isinstance(src, NDArray) \
                else np.asarray(src)
            src = src.astype(np.int32)
            B, L = src.shape
            if vl is None:
                vl_np = np.full((B,), L, np.int32)
            else:
                vl_np = (vl.asnumpy() if isinstance(vl, NDArray)
                         else np.asarray(vl)).astype(np.int32)
            with (_tel.span("estimator.predict_batch") if _tel._ENABLED
                  else _tel.NULL_SPAN):
                futs = [batcher.submit(
                    src[i, :vl_np[i]] if vl_np[i] else src[i, :1])
                    for i in range(B)]
                toks = np.full((B, batcher.max_new), batcher._pad,
                               np.int32)
                lengths = np.zeros((B,), np.int32)
                for i, f in enumerate(futs):
                    got = f.result(timeout=600)
                    n = min(len(got), batcher.max_new)
                    toks[i, :n] = got[:n]
                    lengths[i] = n
            outs.append((_nd.array(toks, dtype="int32"),
                         _nd.array(lengths, dtype="int32")))
        return outs

    # ------------------------------------------------------------- evaluate
    def evaluate(self, val_data):
        for m in self.val_metrics:
            m.reset()
        val_loss = _metric.Loss("val_loss")
        for batch in val_data:
            data, label = _split_batch(batch)
            pred = self.net(data)
            L = self.evaluation_loss(pred, label)
            val_loss.update(0, L)
            for m in self.val_metrics:
                m.update(label, pred)
        return [val_loss] + list(self.val_metrics)

    # ------------------------------------------------------------------ fit
    def fit(self, train_data, val_data=None, epochs=None,
            event_handlers=None, batches=None, batch_size=None,
            prefetch=None, warmup=False):
        """Drive training epochs. ``prefetch=N`` (or ``True``) is the
        opt-in async device feed: each epoch's batches are pulled and
        device_put by a background thread holding up to N staged batches
        (``gluon.data.prefetch.prefetch_to_device``), so the next batch's
        host->device transfer overlaps the current step.

        ``warmup=True`` compiles every batch-shape signature BEFORE the
        timed epochs: the loader is pre-scanned (bounded by
        ``MXTPU_WARMUP_SCAN`` batches) and one forward/backward runs per
        previously-unseen ``(data, label)`` shape, so a bucketed loader
        (``gluon.data.bucketing``) enters epoch 0 with all of its
        programs compiled and zero steady-state recompiles. Pass an
        iterable of ``((data_shape, dtype), (label_shape, dtype))`` pairs
        instead to warm explicit signatures on zero batches (note: aux
        state such as BatchNorm running stats sees the warmup passes)."""
        if epochs is None and batches is None:
            raise MXNetError("fit needs epochs or batches")
        handlers = self._prepare_handlers(event_handlers, val_data, epochs,
                                          batches)
        self.stop_training = False
        if warmup:
            self._warmup(train_data, warmup)

        _dispatch(handlers, "train_begin", self)
        epoch = 0
        while not self.stop_training:
            with (_tel.span("estimator.epoch", {"epoch": epoch})
                  if _tel._ENABLED else _tel.NULL_SPAN):
                _dispatch(handlers, "epoch_begin", self)
                self.train_loss_metric.reset()
                epoch_iter = self._epoch_iter(
                    train_data, prefetch, feed=self.train_step)
                try:
                    for batch in epoch_iter:
                        _dispatch(handlers, "batch_begin", self, batch=batch)
                        if self.train_step is not None:
                            pred = label = None
                            L = self._fused_step(batch)
                        elif _tel._ENABLED:
                            data, label = _split_batch(batch)
                            with _tel.span("estimator.forward_backward"):
                                with autograd.record():
                                    pred = self.net(data)
                                    L = self.loss(pred, label)
                                L.backward()
                            self.trainer.step(_batch_size(batch))
                        else:
                            data, label = _split_batch(batch)
                            with autograd.record():
                                pred = self.net(data)
                                L = self.loss(pred, label)
                            L.backward()
                            self.trainer.step(_batch_size(batch))
                        self.train_loss_metric.update(0, L)
                        _dispatch(handlers, "batch_end", self, batch=batch,
                                  pred=pred, label=label, loss=L)
                        self.stop_training = self.stop_training or any(
                            getattr(h, "stop_training", False)
                            for h in handlers
                        )
                        if self.stop_training:
                            break
                finally:
                    # an abandoned prefetch iterator must retire its
                    # staging thread (early stop / handler exception)
                    if epoch_iter is not train_data and \
                            hasattr(epoch_iter, "close"):
                        epoch_iter.close()
                _dispatch(handlers, "epoch_end", self)
            epoch += 1
            self.stop_training = self.stop_training or any(
                getattr(h, "stop_training", False) for h in handlers
            )
            if hasattr(train_data, "reset"):
                train_data.reset()
        _dispatch(handlers, "train_end", self)
        return self

    # --------------------------------------------------------------- warmup
    def _warmup(self, train_data, warmup):
        """AOT-compile the train path for every batch signature (see
        ``fit``). Parameters receive no optimizer step — only gradients
        (overwritten by the first real backward) and aux state move."""
        from ...base import get_env
        from ... import nd

        def _shape_sig(x):
            return (tuple(x.shape), str(getattr(x, "dtype", "?")))

        if self.train_step is not None:
            # fused path: drive the REAL jitted step per signature
            # (TrainStep.warmup marks the guard steady afterwards)
            with (_tel.span("estimator.warmup") if _tel._ENABLED
                  else _tel.NULL_SPAN):
                if warmup is True:
                    seen = []
                    seen_set = set()
                    cap = get_env("MXTPU_WARMUP_SCAN", 64, int)
                    for i, batch in enumerate(train_data):
                        if i >= cap:
                            break
                        data, label = _split_batch(batch)
                        inputs = tuple(data) if isinstance(
                            data, (list, tuple)) else (data,)
                        sig = tuple(_shape_sig(a) for a in inputs) + (
                            _shape_sig(label),)
                        if sig in seen_set:
                            continue
                        seen_set.add(sig)
                        seen.append(sig)
                    self.train_step.warmup(seen)
                else:
                    self.train_step.warmup(list(warmup))
            return
        with (_tel.span("estimator.warmup") if _tel._ENABLED
              else _tel.NULL_SPAN):
            if warmup is True:
                seen = set()
                cap = get_env("MXTPU_WARMUP_SCAN", 64, int)
                for i, batch in enumerate(train_data):
                    if i >= cap:
                        break
                    data, label = _split_batch(batch)
                    sig = (_shape_sig(data), _shape_sig(label))
                    if sig in seen:
                        continue
                    seen.add(sig)
                    self._warm_one(data, label)
            else:
                for data_spec, label_spec in warmup:
                    (dshape, ddt), (lshape, ldt) = data_spec, label_spec
                    self._warm_one(nd.zeros(dshape, dtype=ddt),
                                   nd.zeros(lshape, dtype=ldt))
        # hybridized nets: further new shapes are accidental recompiles
        co = getattr(self.net, "_cached_op", None)
        if co is not None:
            co._guard.mark_steady()

    def _warm_one(self, data, label):
        _tel.registry().counter("compile/warmup_compiles").inc()
        with autograd.record():
            pred = self.net(data)
            L = self.loss(pred, label)
        L.backward()

    def _fused_step(self, batch):
        """One fused-step dispatch: a pre-placed ``DeviceBatch`` from the
        prefetcher enters directly; raw batches flatten to the step's
        ``(input0, ..., label)`` calling convention."""
        from ...parallel.step import DeviceBatch

        with (_tel.span("estimator.train_step") if _tel._ENABLED
              else _tel.NULL_SPAN):
            if isinstance(batch, DeviceBatch):
                return self.train_step(batch)
            data, label = _split_batch(batch)
            inputs = tuple(data) if isinstance(data, (list, tuple)) \
                else (data,)
            return self.train_step(*inputs, label)

    @staticmethod
    def _epoch_iter(train_data, prefetch, feed=None):
        """One epoch's batch source: raw, or wrapped in the async device
        feed when ``prefetch`` is set (a fresh single-use pipeline per
        epoch — the staging thread dies with the epoch). With ``feed``
        (the fused ``TrainStep``), the prefetcher stages each batch onto
        the step's declared placements — sharded mesh layouts included —
        and yields pre-placed ``DeviceBatch`` objects."""
        if not prefetch:
            return train_data
        from ..data.prefetch import prefetch_to_device

        size = None if prefetch is True else int(prefetch)
        return prefetch_to_device(train_data, size=size, feed=feed)

    def _prepare_handlers(self, event_handlers, val_data, epochs, batches):
        handlers = list(_as_list(event_handlers) if event_handlers else [])
        if not any(isinstance(h, StoppingHandler) for h in handlers):
            handlers.append(StoppingHandler(max_epoch=epochs,
                                            max_batch=batches))
        if not any(isinstance(h, MetricHandler) for h in handlers):
            handlers.append(
                MetricHandler([self.train_loss_metric] + self.train_metrics)
            )
        if val_data is not None and not any(
            isinstance(h, ValidationHandler) for h in handlers
        ):
            handlers.append(ValidationHandler(val_data, self.evaluate))
        return handlers


# ------------------------------------------------------------------ helpers
def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _split_batch(batch):
    if isinstance(batch, (list, tuple)) and len(batch) >= 2:
        return batch[0], batch[1]
    if hasattr(batch, "data") and hasattr(batch, "label"):
        return batch.data[0], batch.label[0]
    raise MXNetError("cannot split batch into (data, label)")


def _batch_size(batch):
    data, _ = _split_batch(batch)
    if isinstance(data, NDArray):
        return data.shape[0]
    return len(data)


def _dispatch(handlers, event, estimator, **kwargs):
    for h in handlers:
        fn = getattr(h, event, None)
        if fn is not None and callable(fn):
            fn(estimator, **kwargs)
