"""Regression tests for the eager bulk queue and the gradient checker.

1. ``_BulkQueue.flush`` cross-queue mutual dependencies must resolve
   entry-by-entry instead of recursing whole-queue flushes to
   ``RecursionError``.
2. ``check_numeric_gradient`` compares autograd with finite differences
   on every backend; nothing in it skips.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import imperative as imp
from mxnet_tpu.test_utils import check_numeric_gradient


def _enqueue(q, key, fn, datas):
    struct = jax.ShapeDtypeStruct((4,), jnp.float32)
    (out,), _ = q.enqueue(key, fn, datas, [struct], False, None)
    return out, out._chunk.data  # (NDArray, _Pending)


class TestBulkQueueCrossFlush:
    def test_mutual_dependency_resolves_without_recursion(self):
        qA, qB = imp._BulkQueue(), imp._BulkQueue()
        a0 = jnp.ones(4)
        oA1, pA1 = _enqueue(qA, "r5A1", lambda x: x + 1, [a0])
        oB1, pB1 = _enqueue(qB, "r5B1", lambda x: x * 2, [pA1])
        oA2, _ = _enqueue(qA, "r5A2", lambda x: x - 3, [pB1])
        # pre-fix: qA.flush -> qB.flush -> qA.flush -> ... RecursionError
        qA.flush()
        assert np.allclose(np.asarray(oA1.data), 2.0)
        assert np.allclose(np.asarray(oB1.data), 4.0)
        assert np.allclose(np.asarray(oA2.data), 1.0)
        qB.flush()
        assert not qA.entries and not qB.entries

    def test_three_queue_cycle(self):
        qA, qB, qC = (imp._BulkQueue() for _ in range(3))
        a0 = jnp.full(4, 2.0)
        oA1, pA1 = _enqueue(qA, "r5cA1", lambda x: x + 1, [a0])
        oB1, pB1 = _enqueue(qB, "r5cB1", lambda x: x * 2, [pA1])
        oC1, pC1 = _enqueue(qC, "r5cC1", lambda x: x + 10, [pB1])
        oA2, _ = _enqueue(qA, "r5cA2", lambda x: x / 2, [pC1])
        qA.flush()
        assert np.allclose(np.asarray(oA2.data), 8.0)  # ((2+1)*2+10)/2
        qB.flush()
        qC.flush()

    def test_same_queue_chain_still_fuses(self):
        q = imp._BulkQueue()
        a0 = jnp.ones(4)
        o1, p1 = _enqueue(q, "r5s1", lambda x: x + 1, [a0])
        o2, _ = _enqueue(q, "r5s2", lambda x: x * 3, [p1])
        q.flush()
        assert np.allclose(np.asarray(o2.data), 6.0)

    def test_foreign_flush_from_consumer_thread(self):
        """A plain (acyclic) cross-queue dependency keeps working: the
        consumer queue's flush resolves the producer queue wholesale."""
        qA, qB = imp._BulkQueue(), imp._BulkQueue()
        oA1, pA1 = _enqueue(qA, "r5fA1", lambda x: x * 5, [jnp.ones(4)])
        oB1, _ = _enqueue(qB, "r5fB1", lambda x: x - 1, [pA1])
        qB.flush()
        assert np.allclose(np.asarray(oB1.data), 4.0)
        assert not qA.entries

    def test_error_in_producing_entry_surfaces(self):
        qA, qB = imp._BulkQueue(), imp._BulkQueue()

        def boom(x):
            raise ValueError("producer exploded")

        oA1, pA1 = _enqueue(qA, "r5eA1", boom, [jnp.ones(4)])
        oB1, pB1 = _enqueue(qB, "r5eB1", lambda x: x, [pA1])
        oA2, _ = _enqueue(qA, "r5eA2", lambda x: x, [pB1])
        with pytest.raises(ValueError, match="producer exploded"):
            qA.flush()
            qB.flush()
            np.asarray(oB1.data)


class TestNumericGradient:
    def test_check_numeric_gradient_cpu_path_unaffected(self):
        check_numeric_gradient(lambda x: (x * x).sum(),
                               [np.random.RandomState(0).rand(5)])
