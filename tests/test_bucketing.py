"""Bucketing input path: FixedBucketSampler + PadToBucket (shape-stable
variable-length batches) and the masked-loss padding invariant."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.data import (DataLoader, FixedBucketSampler,
                                  PadToBucket)


def _lengths(n=120, lo=4, hi=40, seed=0):
    return np.random.RandomState(seed).randint(lo, hi + 1, size=n).tolist()


class TestFixedBucketSampler:
    def test_deterministic_without_shuffle(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, batch_size=8, num_buckets=4)
        assert list(s) == list(s)

    def test_deterministic_under_seed_with_shuffle(self):
        lengths = _lengths()
        np.random.seed(7)
        a = list(FixedBucketSampler(lengths, 8, 4, shuffle=True))
        np.random.seed(7)
        b = list(FixedBucketSampler(lengths, 8, 4, shuffle=True))
        assert a == b

    def test_keep_covers_every_index_once(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, 8, 4, last_batch="keep")
        got = sorted(i for batch in s for i in batch)
        assert got == sorted(range(len(lengths)))
        assert len(list(s)) == len(s)

    def test_discard_drops_ragged_batches(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, 8, 4, last_batch="discard")
        batches = list(s)
        assert all(len(b) == 8 for b in batches)
        assert len(batches) == len(s)

    def test_pad_is_shape_stable_and_covers_all(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, 8, 4, last_batch="pad")
        batches = list(s)
        assert all(len(b) == 8 for b in batches)
        # every index still appears at least once
        assert set(i for b in batches for i in b) == set(range(len(lengths)))

    def test_bucket_membership(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, 8, 4)
        for batch in s:
            ml = max(lengths[i] for i in batch)
            key = next(k for k in s.bucket_keys if ml <= k)
            # every sample in the batch belongs to the same bucket: its
            # length is above the previous boundary
            ki = s.bucket_keys.index(key)
            lo = s.bucket_keys[ki - 1] if ki else 0
            assert all(lo < lengths[i] <= key for i in batch)

    def test_ratio_scales_short_buckets_up(self):
        lengths = _lengths()
        s = FixedBucketSampler(lengths, 8, 4, ratio=0.5)
        assert s.batch_sizes[0] > s.batch_sizes[-1]
        assert s.batch_sizes[-1] == 8
        s0 = FixedBucketSampler(lengths, 8, 4, ratio=0.0)
        assert set(s0.batch_sizes) == {8}

    def test_signatures_match_emitted_shapes(self):
        lengths = _lengths()
        for last in ("keep", "discard", "pad"):
            s = FixedBucketSampler(lengths, 8, 4, ratio=0.5,
                                   last_batch=last)
            p = PadToBucket(s.bucket_keys)
            emitted = set()
            for batch in s:
                data, vl = p([np.zeros(lengths[i], "int32")
                              for i in batch])
                emitted.add(tuple(data.shape))
            assert emitted == {(bs, k) for bs, k in s.signatures()}, last

    def test_too_long_sample_raises(self):
        with pytest.raises(MXNetError):
            FixedBucketSampler([4, 8, 100], 2, bucket_keys=[8, 16])

    def test_stats_renders(self):
        s = FixedBucketSampler(_lengths(), 8, 4)
        assert "FixedBucketSampler" in s.stats()


class TestPadToBucket:
    def test_pads_to_bucket_boundary_with_valid_length(self):
        p = PadToBucket([8, 16], pad_val=0)
        data, vl = p([np.arange(1, 6, dtype="int32"),
                      np.arange(1, 10, dtype="int32")])
        assert data.shape == (2, 16)
        assert vl.asnumpy().tolist() == [5, 9]
        got = data.asnumpy()
        assert got[0, 5:].tolist() == [0] * 11
        assert got[1, 9:].tolist() == [0] * 7

    def test_tuple_samples_per_field_pad_values(self):
        p = PadToBucket([8], pad_val=0, label_pad_val=[0, -1])
        seqs = [np.ones(3, "int32"), np.ones(5, "int32")]
        samples = [(s, s * 2, s * 3) for s in seqs]
        data, vl, tgt, lab = p(samples)
        assert data.shape == tgt.shape == lab.shape == (2, 8)
        assert tgt.asnumpy()[0, 3:].tolist() == [0] * 5
        assert lab.asnumpy()[0, 3:].tolist() == [-1] * 5

    def test_scalar_fields_stack_unpadded(self):
        p = PadToBucket([8])
        data, vl, label = p([(np.ones(3, "int32"), 7),
                             (np.ones(6, "int32"), 9)])
        assert label.shape == (2,)
        assert label.asnumpy().tolist() == [7, 9]

    def test_valid_length_false_matches_step_contract(self):
        p = PadToBucket([8], valid_length=False, label_pad_val=[-1])
        out = p([(np.ones(3, "int32"), np.ones(3, "int32"))])
        assert len(out) == 2  # (data, label) only

    def test_numpy_mode_returns_numpy(self):
        p = PadToBucket([8], numpy=True)
        data, vl = p([np.ones(3, "int32")])
        assert isinstance(data, np.ndarray) and isinstance(vl, np.ndarray)

    def test_overlong_batch_raises(self):
        p = PadToBucket([8])
        with pytest.raises(MXNetError):
            p([np.ones(9, "int32")])


def _masked_ce(logits, label):
    """Masked CE reduced per row then across rows — the benches' loss
    formulation; pad columns contribute exact zeros to each row."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(logits).astype(jnp.float32)
    y = jnp.asarray(label)
    mask = y >= 0
    safe = jnp.where(mask, y, 0).astype(jnp.int32)
    logp = jax.nn.log_softmax(x, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    row = jnp.where(mask, nll, 0.0).sum(axis=-1)
    return row.sum() / mask.sum()


class TestMaskedLossPaddingInvariant:
    def test_padded_vs_unpadded_bit_identical(self):
        import jax

        f = jax.jit(_masked_ce)
        rng = np.random.RandomState(0)
        B, S, S2, V = 4, 11, 16, 13
        logits = rng.randn(B, S, V).astype("float32")
        label = rng.randint(0, V, (B, S)).astype("int32")
        lens = [5, 11, 8, 3]
        for i, n in enumerate(lens):
            label[i, n:] = -1
        # pad with GARBAGE logits and -1 labels: the loss may not see any
        # of it. The two shapes are two programs, one reduction order
        # each, so the sums are equal to float32 rounding and not to the
        # bit (read on this JAX: 1 ulp)
        logits_p = np.concatenate(
            [logits, rng.randn(B, S2 - S, V).astype("float32")], axis=1)
        label_p = np.concatenate(
            [label, np.full((B, S2 - S), -1, "int32")], axis=1)
        a = np.asarray(f(logits, label))
        b = np.asarray(f(logits_p, label_p))
        np.testing.assert_array_max_ulp(a, b, maxulp=4)
        # and the garbage is not seen at all: other garbage, the same bits
        logits_q = np.concatenate(
            [logits, rng.randn(B, S2 - S, V).astype("float32")], axis=1)
        assert np.asarray(f(logits_q, label_p)).tobytes() == b.tobytes()

    def test_trainstep_losses_bit_identical_padded_vs_unpadded(self):
        """End to end through TrainStep: the same sentences fed at their
        natural length and padded to a larger bucket give bitwise equal
        losses (identical params; masked loss; no dropout)."""
        import jax.numpy as jnp

        from mxnet_tpu import gluon, nd, optimizer as opt
        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.parallel import TrainStep

        class _Loss:
            def __call__(self, pred, label):
                return NDArray(_masked_ce(pred.data, label.data))

        def build():
            mx.random.seed(5)
            np.random.seed(5)
            net = gluon.nn.Dense(8, flatten=False)
            net.initialize()
            net(nd.zeros((2, 4, 3)))
            return TrainStep(net, _Loss(),
                             opt.SGD(learning_rate=0.0), donate=False)

        rng = np.random.RandomState(1)
        x = rng.randn(2, 5, 3).astype("float32")
        y = rng.randint(0, 8, (2, 5)).astype("int32")
        y[0, 3:] = -1
        x_p = np.concatenate(
            [x, rng.randn(2, 3, 3).astype("float32")], axis=1)
        y_p = np.concatenate([y, np.full((2, 3), -1, "int32")], axis=1)
        l1 = build()(nd.array(x), nd.array(y)).asnumpy()
        l2 = build()(nd.array(x_p), nd.array(y_p)).asnumpy()
        assert l1.tobytes() == l2.tobytes()


class TestDataLoaderComposition:
    def test_bucketed_loader_emits_only_signature_shapes(self):
        lengths = _lengths(n=80)
        rng = np.random.RandomState(0)
        dataset = [(rng.randint(1, 50, size=n).astype("int32"),
                    rng.randint(0, 5)) for n in lengths]
        s = FixedBucketSampler(lengths, 8, 4, ratio=0.5, last_batch="pad")
        loader = DataLoader(dataset, batch_sampler=s,
                            batchify_fn=PadToBucket(s.bucket_keys))
        shapes = set()
        for data, vl, label in loader:
            shapes.add(tuple(data.shape))
            assert int(vl.asnumpy().max()) <= data.shape[1]
        assert shapes == {(bs, k) for bs, k in s.signatures()}

    def test_composes_with_prefetch_to_device(self):
        lengths = _lengths(n=40)
        rng = np.random.RandomState(0)
        dataset = [rng.randint(1, 50, size=n).astype("int32")
                   for n in lengths]
        s = FixedBucketSampler(lengths, 8, 2, last_batch="discard")
        loader = DataLoader(dataset, batch_sampler=s,
                            batchify_fn=PadToBucket(s.bucket_keys),
                            prefetch_to_device=2)
        n = 0
        for data, vl in loader:
            assert data.shape[1] in s.bucket_keys
            n += 1
        assert n == len(s)
