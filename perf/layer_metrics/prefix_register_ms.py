"""Time per iteration spent donating retiring requests' pages to the prefix
trie (``mxtpu.sched.register_prefix``, ``stats["register_prefix_s"]``),
the device round trips of ``prefix_readback_ms`` included."""

from perf.harness.phases import per_iteration_ms

NAME = "prefix_register_ms"
UNIT = "ms"
LAYER = "prefix cache"
MOVES = "serve_tokens_per_s"


def read(run):
    return per_iteration_ms(run, ("register_prefix_s",))
