"""Regime benchmark: blocked ``linear_cross_entropy`` vs materialized
logits (find the regime where the blocked op wins, or prove there is none
on this chip).

Sweeps V x (B*S), forward+backward per step, profiler device timing.

    python -m benchmarks.bench_linear_ce [--quick]
"""

from __future__ import annotations

import argparse

import numpy as np


def device_ms(fn, args, iters=6):
    """Profiler-sum of device op time per call, in ms (shared helper:
    metadata-driven lane detection lives in benchmarks/common.py)."""
    from .common import device_us

    us = device_us(fn, args, iters=iters)
    return us / 1e3 if us is not None else float("nan")


def main():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.fused_loss import linear_cross_entropy

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    D = 768
    Vs = [30522, 131072] if args.quick else [30522, 131072, 262144]
    Ns = [8192] if args.quick else [8192, 32768]
    rng = np.random.RandomState(0)
    print(f"| V | B*S | naive ms | fused ms | winner |")
    print(f"|---|---|---|---|---|")
    results = []
    for V in Vs:
        for N in Ns:
            x = jnp.asarray(rng.rand(N, D).astype(np.float32)).astype(jnp.bfloat16)
            w = jnp.asarray((rng.rand(V, D).astype(np.float32) - 0.5) * 0.02).astype(jnp.bfloat16)
            y = jnp.asarray(rng.randint(0, V, N).astype(np.int32))

            def naive_loss(x, w, y):
                logits = jnp.dot(x, w.T,
                                 preferred_element_type=jnp.float32)
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                lab = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
                return jnp.mean(lse - lab)

            def fused_loss(x, w, y):
                return jnp.mean(linear_cross_entropy(x, w, y))

            naive = jax.jit(jax.grad(naive_loss, argnums=(0, 1)))
            fused = jax.jit(jax.grad(fused_loss, argnums=(0, 1)))
            try:
                t_n = device_ms(naive, (x, w, y))
            except Exception as e:  # OOM at large V*N
                t_n = float("inf")
                print(f"naive failed at V={V} N={N}: {type(e).__name__}",
                      flush=True)
            t_f = device_ms(fused, (x, w, y))
            win = "fused" if t_f < t_n else "naive"
            print(f"| {V} | {N} | {t_n:.2f} | {t_f:.2f} | {win} |",
                  flush=True)
            results.append((V, N, t_n, t_f))
    return results


if __name__ == "__main__":
    main()
