"""Time per iteration the prefix registration waits on the device: building
the ``c[slot]`` slices and ``jax.device_get`` of a new root's cross frames
(``mxtpu.sched.register_prefix.readback``, ``register_readback_s``)."""

from perf.harness.phases import per_iteration_ms

NAME = "prefix_readback_ms"
UNIT = "ms"
LAYER = "prefix cache"
MOVES = "serve_tokens_per_s"


def read(run):
    return per_iteration_ms(run, ("register_readback_s",))
