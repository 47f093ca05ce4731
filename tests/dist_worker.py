"""Worker body for the multi-process KVStoreDist test (run via
tools/launch.py local launcher; reference tested dist kvstore exactly this
way — localhost multi-process, ``tests/nightly/dist_sync_kvstore.py``
[unverified])."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# pin the CPU platform through the config API, which holds whatever the
# environment names (same as conftest.py)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    mode = os.environ.get("DIST_TEST_MODE", "basic")
    kv = mx.kv.create("dist_sync")
    rank, nworkers = kv.rank, kv.num_workers
    assert nworkers >= 2, f"expected >=2 workers, got {nworkers}"

    if mode == "crash":
        # worker 1 dies mid-job; the launcher must propagate the failure
        # and terminate the others rather than leave them hung
        kv.init("0", nd.zeros((2,)))
        if rank == 1:
            print("worker 1: simulating crash")
            os._exit(17)
        import time as _t
        _t.sleep(30)  # would hang forever without launcher propagation
        return 0

    if mode == "full":
        # compression + updater-on-store over dist_sync (the reference's
        # nightly dist_sync_kvstore coverage at 4 workers)
        from mxnet_tpu import optimizer as opt

        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        kv.set_optimizer(opt.SGD(learning_rate=0.5))
        kv.init("w", nd.ones((6, 2)))
        for step in range(3):
            kv.push("w", nd.ones((6, 2)))  # grad 1 (above threshold)
            out = nd.zeros((6, 2))
            kv.pull("w", out=out)
        # updater-on-store arithmetic is fully deterministic here: the
        # 2-bit compressor quantizes grad 1.0 (>= threshold 0.5) to +0.5
        # per worker, the store sums nworkers * 0.5 = 2.0 and applies
        # w <- w - lr * 2.0 per step: 1 - 3 * 0.5 * 2 = -2 after 3 steps.
        # Every worker asserting the exact value IS the cross-worker
        # agreement check (a plain push/pull comparison would itself go
        # through the updater).
        expect_w = 1.0 - 3 * 0.5 * (0.5 * nworkers)
        np.testing.assert_allclose(out.asnumpy(),
                                   np.full((6, 2), expect_w), rtol=1e-5)
        # round-4 wire-byte check: the cross-host transfer must carry
        # PACKED 2-bit codes, not floats — 12 values -> 3 uint8 bytes
        # per worker (vs 48 f32 bytes uncompressed)
        assert getattr(kv, "last_push_wire_bytes", None) == 3, \
            f"wire bytes {getattr(kv, 'last_push_wire_bytes', None)} != 3"
        print(f"worker {rank}/{nworkers}: full-mode dist kvstore OK "
              f"(wire bytes/worker: {kv.last_push_wire_bytes})")
        return 0

    if mode == "async":
        # bounded-staleness dist_async (round-5): local apply, stale
        # reads, parameter-averaging reconcile at the bound
        from mxnet_tpu import optimizer as opt

        lr = 0.1
        bound = int(os.environ["MXTPU_ASYNC_STALENESS_BOUND"])
        assert bound == 2
        kv2 = mx.kv.create("dist_async")
        kv2.set_optimizer(opt.SGD(learning_rate=lr))
        kv2.init("w", nd.ones((3,)))
        g = rank + 1.0  # workers push DIFFERENT gradients

        # push 1: applied locally, NO reconcile -> replicas DIVERGE
        kv2.push("w", nd.ones((3,)) * g)
        out = nd.zeros((3,))
        kv2.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0 - lr * g, rtol=1e-5)

        # push 2 hits the bound: local apply THEN average across workers
        kv2.push("w", nd.ones((3,)) * g)
        kv2.pull("w", out=out)
        locals_ = [1.0 - lr * 2 * (r + 1) for r in range(nworkers)]
        want = sum(locals_) / nworkers
        np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-5)

        # push 3: diverges again from the common reconciled base
        kv2.push("w", nd.ones((3,)) * g)
        kv2.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), want - lr * g, rtol=1e-5)
        print(f"worker {rank}/{nworkers}: dist_async bounded-staleness OK")
        return 0

    # init must be identical on all workers (reference requirement)
    kv.init("0", nd.zeros((4, 3)))
    kv.init("big", nd.ones((8,)) * 100)

    # each worker pushes rank+1; dist_sync must deliver sum over workers
    kv.push("0", nd.ones((4, 3)) * (rank + 1))
    out = nd.zeros((4, 3))
    kv.pull("0", out=out)
    expect = sum(r + 1 for r in range(nworkers))
    np.testing.assert_allclose(out.asnumpy(), np.full((4, 3), expect), rtol=1e-6)

    # barrier then second round on another key to check repeated sync
    kv.barrier()
    kv.push("big", nd.ones((8,)) * rank)
    out2 = nd.zeros((8,))
    kv.pull("big", out=out2)
    expect2 = sum(range(nworkers))
    np.testing.assert_allclose(out2.asnumpy(), np.full((8,), expect2), rtol=1e-6)

    print(f"worker {rank}/{nworkers}: dist kvstore OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
