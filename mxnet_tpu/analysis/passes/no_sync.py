"""no-sync pass: the jitted hot paths must never block on the device.

The rule sets and targets of the sync lint of PRs 2, 5 and 8, on the pass
framework (``python tools/mxlint.py --passes no-sync``). Any host
synchronization (``.asnumpy()``, ``float(loss)``, ``np.asarray`` on a
device array, ``block_until_ready``, ``time.sleep``) inside a dispatch
path silently serializes the pipeline against the device; this walks the
AST of the listed (file, class, methods) targets and flags blocking
calls.

The serving targets carry a second rule set (``DISPATCH_TARGETS``): a
dispatch on the scheduler's pass is exactly one enqueue, so no *eager
device constructor* may stand before the compiled call. Each
``jnp.asarray`` of a host array is a host-to-device put of its own, each
``jax.random.PRNGKey`` a string of tiny programs, and all of them wait in
the device queue the pass's program waits in. Host operands go to the
compiled call as numpy, and the key is made inside the program from an
integer seed.
"""

from __future__ import annotations

import ast
import os

from ..core import AnalysisPass, REPO_ROOT, register

STEP_PY = "mxnet_tpu/parallel/step.py"
INFER_PY = "mxnet_tpu/parallel/infer.py"
BATCHER_PY = "mxnet_tpu/serving/batcher.py"

# the train-step fast-path bodies: __call__ (DeviceBatch detection +
# dispatch) and _dispatch (the staged-operand hot dispatch). _stage is
# deliberately NOT linted — it is the slow path the fast path skips.
FAST_PATH_FUNCS = ("__call__", "_dispatch")

# every linted (file, class, methods) hot path. The inference engine's
# decode_n is the whole generation dispatch and decode_iter/prefill_paged
# are the continuous-batching iteration dispatches; the scheduler's
# _dispatch assembles and fires the decode burst (ContinuousBatcher.
# _collect and _admit are the designated sync points and stay
# unlinted). ContinuousBatcher._step_once — the scheduler loop body
# — is linted too: its syncs must stay delegated to those named phases,
# never inlined next to a dispatch. So is the whole retire path (_retire,
# the root registration and its store dispatch, each request's trie
# insert): a new root's cross frames stay on the device, and retiring a
# request reads nothing back. _apply_prefix_hits is the one batched
# adoption dispatch of an admission group. The burst dispatched AHEAD of
# the last one's read-back is linted as _dispatch is, with the rule that
# allows it (_may_run_ahead: state the scheduler holds, nothing read from
# the device), and so is the engine's next_carry, the program that makes
# its operands on the device: a sync in any of the three would put the
# host's turn back between two bursts.
SCHEDULER_FUNCS = ("_dispatch", "_may_run_ahead", "_dispatch_ahead",
                   "_step_once", "_retire", "_register_roots",
                   "_store_rows", "_register_prefix", "_apply_prefix_hits")
TARGETS = (
    (STEP_PY, "TrainStep", FAST_PATH_FUNCS),
    (INFER_PY, "InferStep", ("__call__", "_dispatch", "decode_n",
                             "decode_iter", "next_carry", "prefill_paged",
                             "prefill_suffix_paged", "spec_draft",
                             "spec_verify")),
    (BATCHER_PY, "ContinuousBatcher", SCHEDULER_FUNCS),
)

# the serving dispatch paths, where the second rule set holds too: the
# paged entry points of the engine and everything the scheduler runs
# around its own compiled calls. (decode_n and __call__ stage their
# operands on the device by design and stay under the first set alone.)
DISPATCH_TARGETS = (
    (INFER_PY, "InferStep", ("decode_iter", "next_carry", "prefill_paged",
                             "prefill_suffix_paged", "spec_draft",
                             "spec_verify")),
    (BATCHER_PY, "ContinuousBatcher", SCHEDULER_FUNCS),
)

# method attributes that force a device->host readback / host sync
BLOCKING_ATTRS = {
    "asnumpy", "asscalar", "item", "tolist", "block_until_ready",
    "copy_to_host_async",
}
# bare builtins that coerce a device scalar on the host
BLOCKING_BUILTINS = {"float", "int", "bool", "complex", "print"}
# module.attr calls that materialize device arrays on host (np.asarray on
# a device array round-trips it) or stall the thread
BLOCKING_QUALIFIED = {
    ("np", "asarray"), ("_np", "asarray"), ("numpy", "asarray"),
    ("np", "array"), ("_np", "array"), ("numpy", "array"),
    ("jax", "device_get"), ("time", "sleep"), ("_time", "sleep"),
}
# eager device constructors, by the dotted name's last two parts: each
# call is a put or a small program of its own launched from Python
EAGER_MARK = "eager device constructor"  # in every message of this set
EAGER_CONSTRUCTORS = {
    ("jnp", "asarray"), ("jnp", "array"), ("jnp", "float32"),
    ("jnp", "int32"), ("jnp", "bool_"), ("jax", "device_put"),
    ("random", "PRNGKey"), ("random", "key"),
}


def blocking_calls_in(fn: ast.FunctionDef, label: str):
    """[(lineno, message)] for blocking calls anywhere in ``fn``."""
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in BLOCKING_BUILTINS:
            out.append((node.lineno,
                        f"{label}: host coercion {f.id}(...) blocks on "
                        "the device value"))
        elif isinstance(f, ast.Attribute):
            if f.attr in BLOCKING_ATTRS:
                out.append((node.lineno,
                            f"{label}: .{f.attr}() forces a device->host "
                            "sync"))
            elif isinstance(f.value, ast.Name) and \
                    (f.value.id, f.attr) in BLOCKING_QUALIFIED:
                out.append((node.lineno,
                            f"{label}: {f.value.id}.{f.attr}(...) "
                            "materializes/stalls on host"))
    return out


def eager_constructors_in(fn: ast.FunctionDef, label: str):
    """[(lineno, message)] for eager device constructors anywhere in
    ``fn`` (``jax.random.PRNGKey`` matches by its last two names)."""
    out = []
    for node in ast.walk(fn):
        f = node.func if isinstance(node, ast.Call) else None
        if not isinstance(f, ast.Attribute):
            continue
        owner = f.value
        owner = owner.id if isinstance(owner, ast.Name) else \
            owner.attr if isinstance(owner, ast.Attribute) else None
        if (owner, f.attr) in EAGER_CONSTRUCTORS:
            out.append((node.lineno,
                        f"{label}: {EAGER_MARK} "
                        f"{ast.unparse(f)}(...) is an enqueue of its own "
                        "before the dispatch"))
    return out


def find_violations(path=None, class_name: str = "TrainStep",
                    funcs=FAST_PATH_FUNCS, dispatch_funcs=()):
    """Return [(lineno, message)] for blocking calls inside the given
    class's listed method bodies, and for eager device constructors
    inside those of them that ``dispatch_funcs`` names (tool-compatible
    entry point; ``path`` may be absolute or repo-relative)."""
    if path is None:
        path = os.path.join(REPO_ROOT, STEP_PY)
    elif not os.path.isabs(path):
        path = os.path.join(REPO_ROOT, path)
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    out = []
    classes = [n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == class_name]
    if not classes:
        return [(0, f"{class_name} class not found in {path}")]
    fns = [n for n in classes[0].body
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
           and n.name in funcs]
    missing = set(funcs) - {f.name for f in fns}
    if missing:
        out.append((classes[0].lineno,
                    f"{class_name} hot-path method(s) {sorted(missing)} "
                    "not found — update TARGETS if the hot path was "
                    "renamed"))
    for fn in fns:
        out.extend(blocking_calls_in(fn, f"{class_name}.{fn.name}"))
        if fn.name in dispatch_funcs:
            out.extend(eager_constructors_in(fn, f"{class_name}.{fn.name}"))
    return sorted(out)


def find_all_violations():
    """Lint every TARGETS entry, the serving ones under both rule sets;
    returns [(path, lineno, message)]."""
    dispatch = {(p, cls): funcs for p, cls, funcs in DISPATCH_TARGETS}
    out = []
    for path, cls, funcs in TARGETS:
        for lineno, msg in find_violations(path, cls, funcs,
                                           dispatch.get((path, cls), ())):
            out.append((path, lineno, msg))
    return out


@register
class NoSyncPass(AnalysisPass):
    name = "no-sync"
    ir = "ast"
    description = ("jitted train/inference/serving hot paths stay free "
                   "of blocking host syncs, serving dispatches of eager "
                   "device constructors")

    def run(self, ctx):
        return [self.finding(
            "eager-constructor" if EAGER_MARK in msg
            else "blocking-call", path, lineno,
            key=msg.split(":")[0] + ":" + msg.split(":", 2)[-1][:60],
            message=msg)
            for path, lineno, msg in find_all_violations()]
